"""The comparison that decides `correct`: the system's training state and
losses against the float32 reference's, by norms of each leaf.

Numbers (each with a limit of its own, kept in the cell's workload file;
a cell compares the numbers its workload file gives limits for):

  loss_gap    worst |loss - ref| / |ref| over the losses compared;
  moment_gap  worst leaf of | |m| - |m_ref| | / max(|m_ref|, median leaf
              |m_ref|), Adam's first moment after the steps compared,
              i.e. the gradients as the optimizer got them;
  moment_err  worst leaf of |m - m_ref| / max(|m_ref|, median leaf
              |m_ref|): the norm of the difference, which a change of
              precision moves at first order where a gap of norms moves
              only at second;
  update_gap  the same gap of norms for the params' change from the
              seeded init, | |p - p0| - |p_ref - p0| |, over the leaves
              whose first reference gradient is at least a thousandth of
              the median leaf's (a leaf with next to no gradient moves
              under Adam by round-off alone);
  roundtrip   leaves of the last save that differ, bit for bit, from the
              state the call returned (exact: limit 0);
  counters    |step - n| + |Adam count - n| of the state the call
              returned after n steps (exact: limit 0).

Norms are taken leaf by leaf in float64 on the host.
"""
from __future__ import annotations

import math

import numpy as np

#: leaves whose first reference gradient norm is under this share of the
#: median leaf's are left out of update_gap
TINY_GRAD = 1e-3
_CHUNK = 1 << 24


def norm64(x, y=None) -> float:
    """L2 norm of x (or of x - y) in float64, in chunks."""
    a = np.asarray(x).reshape(-1)
    b = None if y is None else np.asarray(y).reshape(-1)
    tot = 0.0
    for i in range(0, a.size, _CHUNK):
        d = a[i:i + _CHUNK].astype(np.float64)
        if b is not None:
            d -= b[i:i + _CHUNK].astype(np.float64)
        tot += float(np.dot(d, d))
    return math.sqrt(tot)


def flat_names(tree, prefix: str = "") -> dict:
    """Nested dicts and lists -> {"a/b/0/c": leaf}."""
    items = (tree.items() if isinstance(tree, dict) else enumerate(tree))
    out = {}
    for k, v in items:
        name = f"{prefix}{k}"
        if isinstance(v, (dict, list, tuple)):
            out.update(flat_names(v, name + "/"))
        else:
            out[name] = v
    return out


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """{leaf: | |prog| - |ref| | / max(|ref|, median |ref|)} over ref's
    leaves; prog/ref: {leaf: norm}.  A leaf missing or not finite in
    prog reads inf."""
    med = float(np.median(list(ref.values())))
    return {k: (abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
                if k in prog and math.isfinite(prog[k]) else math.inf)
            for k in ref}


def worst_leaf_gap(prog: dict, ref: dict):
    """(worst gap, leaf) of ``leaf_gaps``."""
    gaps = leaf_gaps(prog, ref)
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def numbers(prog: dict, ref: dict) -> dict:
    """prog: {"losses": {step: loss}, and where present "params" and "m"
    ({leaf: np}), "roundtrip" ((count, names)), "counters" ((step, count))
    with "n_steps"}; ref: what ``reference.llama.run`` returns.  Returns
    {name: (value, detail)}."""
    out = {}
    gaps = []
    for t, loss in sorted(prog["losses"].items()):
        r = ref["losses"][t]
        g = abs(loss - r) / abs(r) if math.isfinite(loss) else math.inf
        gaps.append((g, f"step {t}: {loss!r} vs {r!r}"))
    out["loss_gap"] = max(gaps) if gaps else (math.inf, "no loss")

    if "m" in prog:
        m_p = {k: norm64(x) for k, x in prog["m"].items()}
        m_r = {k: norm64(x) for k, x in ref["m"].items()}
        out["moment_gap"] = worst_leaf_gap(m_p, m_r)
        m_d = {k: norm64(prog["m"][k], ref["m"][k]) + m_r[k]
               for k in m_r if k in prog["m"]}
        # |m - m_ref| as a gap against |m_ref|: err = (d + r) - r
        out["moment_err"] = worst_leaf_gap(m_d, m_r)

        g1 = ref["grad1_norms"]
        med = float(np.median(list(g1.values())))
        moved = [k for k in ref["params"] if g1[k] >= TINY_GRAD * med]
        d_p = {k: norm64(prog["params"][k], ref["p0"][k]) for k in moved
               if k in prog["params"]}
        d_r = {k: norm64(ref["params"][k], ref["p0"][k]) for k in moved}
        out["update_gap"] = worst_leaf_gap(d_p, d_r)
    if "roundtrip" in prog:
        count, names = prog["roundtrip"]
        out["roundtrip"] = (float(count), f"differ: {names}")
    if "counters" in prog:
        step, count = prog["counters"]
        n = prog["n_steps"]
        out["counters"] = (float(abs(step - n) + abs(count - n)),
                           f"step {step}, Adam count {count}, of {n}")
    return out


def judge(nums: dict, limits: dict):
    """(correct, checks): every number at or under its limit."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value, detail = nums.get(name, (math.inf, "not computed"))
        passed = math.isfinite(value) and value <= limit
        ok = ok and passed
        checks[name] = {"value": value, "limit": limit,
                        "at": str(detail), "ok": passed}
    return ok, checks
