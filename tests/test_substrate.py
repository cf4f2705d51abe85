"""Optimizer, sharding rules, HLO analyzer, serving engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.optim.adamw import (AdamWCfg, adamw_update, cosine_schedule,
                               global_norm, init_opt_state)


# ------------------------------------------------------------------- adamw

def _np_adamw_step(p, g, m, v, t, lr, b1=0.9, b2=0.95, eps=1e-8, wd=0.1):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return p - lr * (mh / (np.sqrt(vh) + eps) + wd * p), m, v


def test_adamw_matches_numpy_reference():
    rng = np.random.default_rng(0)
    p = {"w": jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))}
    g = {"w": jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32) * 0.01)}
    opt = init_opt_state(p)
    cfg = AdamWCfg(clip_norm=1e9)           # disable clip for the comparison
    pn, optn, _ = adamw_update(p, g, opt, lr=1e-3, cfg=cfg)
    ref, m, v = _np_adamw_step(np.asarray(p["w"]), np.asarray(g["w"]),
                               np.zeros((8, 4)), np.zeros((8, 4)), 1, 1e-3)
    np.testing.assert_allclose(np.asarray(pn["w"]), ref, rtol=1e-5)
    # second step
    pn2, optn2, _ = adamw_update(pn, g, optn, lr=1e-3, cfg=cfg)
    ref2, _, _ = _np_adamw_step(ref, np.asarray(g["w"]), m, v, 2, 1e-3)
    np.testing.assert_allclose(np.asarray(pn2["w"]), ref2, rtol=1e-5)


def test_grad_clipping_scales_update():
    p = {"w": jnp.zeros((4,))}
    g = {"w": jnp.full((4,), 100.0)}
    opt = init_opt_state(p)
    _, _, metrics = adamw_update(p, g, opt, lr=1.0,
                                 cfg=AdamWCfg(clip_norm=1.0))
    assert float(metrics["grad_norm"]) == pytest.approx(200.0)
    assert float(metrics["clip_scale"]) == pytest.approx(1 / 200.0, rel=1e-4)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=110, min_frac=0.1)
    assert float(lr(jnp.int32(0))) == pytest.approx(0.1)   # (s+1)/warmup
    assert float(lr(jnp.int32(10))) == pytest.approx(1.0)
    assert float(lr(jnp.int32(110))) == pytest.approx(0.1, abs=1e-6)
    assert float(lr(jnp.int32(60))) == pytest.approx(0.55, abs=0.02)


@settings(max_examples=20, deadline=None)
@given(st.floats(1e-3, 1e3))
def test_global_norm_property(scale):
    t = {"a": jnp.ones((3,)) * scale, "b": jnp.zeros((2,))}
    assert float(global_norm(t)) == pytest.approx(np.sqrt(3) * scale, rel=1e-5)


# ---------------------------------------------------------- sharding rules

def test_resolve_spec_divisibility_and_prefix(tmp_path):
    import subprocess, sys, json, os
    snippet = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from jax.sharding import PartitionSpec as P
from repro.distributed.sharding import make_variant, resolve_spec
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
r = make_variant("baseline")
checks = []
# divisible head dim shards on model
checks.append(resolve_spec(("embed", "heads", None), (64, 8, 16), mesh, r)
              == P(None, "model", None))
# non-divisible (9 heads vs 4) stays replicated
checks.append(resolve_spec(("embed", "heads", None), (64, 9, 16), mesh, r)
              == P(None, None, None))
# batch joint ("pod","data") degrades to ("data",) -- pod absent
checks.append(resolve_spec(("batch", "seq"), (6, 128), mesh, r)
              == P("data", None))
# joint prefix fallback in dponly: batch=6 not divisible by 8 -> data only
d = make_variant("dponly")
checks.append(resolve_spec(("batch", None), (6, 4), mesh, d) == P("data", None))
# a mesh axis is never used twice in one spec
spec = resolve_spec(("heads", "ffn"), (8, 8), mesh, r)
checks.append(spec == P("model", None))
# fsdp extends the largest replicated dim over data
f = make_variant("fsdp")
spec = resolve_spec(("embed", "ffn"), (64, 8), mesh, f, fsdp=True)
checks.append(spec == P("data", "model"))
print(json.dumps(checks))
"""
    r = subprocess.run([sys.executable, "-c", snippet], capture_output=True,
                       text=True, timeout=240,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    assert all(json.loads(r.stdout.strip().splitlines()[-1]))


def test_variant_registry():
    from repro.distributed.sharding import make_variant
    for name in ("baseline", "fsdp", "kvseq", "seqshard", "expert_ff",
                 "dponly", "dponly_fsdp"):
        v = make_variant(name)
        assert v.name in (name, "baseline")
    with pytest.raises(KeyError):
        make_variant("nope")


# ------------------------------------------------------------ hlo analyzer

def test_hlo_analyzer_counts_scan_trips():
    """The analyzer must multiply while-body costs by trip count (the raw
    cost_analysis famously does not)."""
    from repro.launch.hlo_analysis import analyze
    L, D, B = 8, 128, 32

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    ws = jnp.ones((L, D, D), jnp.float32)
    x = jnp.ones((B, D), jnp.float32)
    compiled = jax.jit(f).lower(ws, x).compile()
    cost = analyze(compiled.as_text())
    analytic = 2 * B * D * D * L
    assert cost.flops > 0.9 * analytic, (cost.flops, analytic)
    assert cost.flops < 3.0 * analytic, (cost.flops, analytic)
    assert cost.unresolved_whiles == 0


def test_hlo_analyzer_parses_synthetic_module():
    from repro.launch.hlo_analysis import analyze, parse_hlo, type_bytes
    text = """
HloModule test

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  %w = f32[4,4]{1,0} get-tuple-element(%p), index=1
  %w2 = f32[4,4]{1,0} dot(%w, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[4,4]{1,0} all-reduce(%w2), replica_groups={{0,1}}, to_apply=%body
  ROOT %t = (s32[], f32[4,4]{1,0}) tuple(%i2, %ar)
}

%cond (p2: (s32[], f32[4,4])) -> pred[] {
  %p2 = (s32[], f32[4,4]{1,0}) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i3, %n), direction=LT
}

ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4]{1,0} parameter(0)
  %z = s32[] constant(0)
  %tup = (s32[], f32[4,4]{1,0}) tuple(%z, %a)
  %loop = (s32[], f32[4,4]{1,0}) while(%tup), condition=%cond, body=%body
  ROOT %out = f32[4,4]{1,0} get-tuple-element(%loop), index=1
}
"""
    assert type_bytes("f32[4,4]{1,0}") == 64
    assert type_bytes("(s32[], f32[4,4])") == 4 + 64
    cost = analyze(text, pod_size=1)
    # dot flops = 2*4*4*4 = 128 per trip, 5 trips
    assert cost.flops >= 128 * 5
    assert cost.coll_bytes == 64 * 5
    assert cost.coll_count == 5


# ------------------------------------------------------------------- serve

@pytest.mark.slow
def test_serve_engine_greedy_matches_forward_argmax():
    from repro.configs import ARCHS, reduce_for_smoke
    from repro.distributed.sharding import make_variant
    from repro.launch.mesh import make_local_mesh
    from repro.models.layers import Policy
    from repro.models.params import init_params
    from repro.models.registry import get_api
    from repro.serve.engine import ServeEngine

    cfg = reduce_for_smoke(ARCHS["smollm-135m"])
    api = get_api(cfg)
    params = init_params(api.param_defs(cfg, 48), jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, make_local_mesh(), make_variant("baseline"),
                      max_seq=48, policy=Policy(compute=jnp.float32))
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    res = eng.generate(prompts, 6)
    assert res.tokens.shape == (2, 6)
    # teacher-forcing check: replay prompt+generated through forward; the
    # greedy choice at each position must match
    seq = np.concatenate([prompts, res.tokens], axis=1)
    full, _ = api.forward(cfg, params,
                          {"tokens": jnp.asarray(seq)},
                          Policy(compute=jnp.float32))
    for t in range(6):
        pos = prompts.shape[1] + t - 1
        logits = np.asarray(full[:, pos])
        pred = np.argmax(logits, axis=-1)
        for b in range(logits.shape[0]):
            if pred[b] == res.tokens[b, t]:
                continue
            # The decode path (incremental KV cache) and the full forward
            # reduce in different orders; when the top-2 logits are within
            # float32 noise the argmax can legitimately flip.  Only a gap
            # beyond noise is a real cache/position bug.
            gap = logits[b, pred[b]] - logits[b, res.tokens[b, t]]
            assert gap < 1e-2, (
                f"t={t} b={b}: decode chose {res.tokens[b, t]} but forward "
                f"argmax is {pred[b]} with logit gap {gap:.4f} (beyond "
                f"float32 tie noise -- KV-cache divergence)")
