#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process:

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        [--sound 1,2,...] [--control 1,2,3] [--faults half_batch,...] \\
        [--fault-seeds 1,2,3]

  --sound     full runs of the cell (``run.main``) on these seeds: the
              lower readings;
  --control   the float32 reference with every matrix product in float8
              (``precision="fp8"``) put in the system's place, against
              the float32 reference, on these seeds: the upper readings
              (the losses the window would log and, in a cell that saves,
              the params and moments of its first save);
  --faults    full runs with each fault of ``faults.py`` planted, on
              ``--fault-seeds``.

One JSON line per reading: {"kind", "seed", "numbers", "correct"}.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import io
import json
import sys
from contextlib import redirect_stdout
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None, require_tpu: bool = True, base=None,
         bench_file=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--sound", type=_ints, default=[])
    ap.add_argument("--control", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import compare, faults, harness, run

    def full(kind, seed):
        buf = io.StringIO()
        try:
            with redirect_stdout(buf):
                rc = run.main(["--workload", args.workload, "--seed",
                               str(seed), "--seconds", str(args.seconds),
                               "--trace", "0"], require_tpu=require_tpu,
                              base=base, bench_file=bench_file)
        except Exception as e:          # a run that crashes has failed
            rc = f"{type(e).__name__}: {e}"
        recs = [json.loads(ln) for ln in buf.getvalue().splitlines()
                if ln.startswith("{")]
        res = recs[-1] if rc == 0 and recs else {}
        nums = [r for r in recs if r.get("phase") == "compared"]
        print(json.dumps({"kind": kind, "seed": seed, "rc": rc,
                          "correct": res.get("correct"),
                          "numbers": {k: v for k, v in nums[-1].items()
                                      if k not in ("phase", "seconds")}
                          if nums else {},
                          "metrics": res.get("metrics")}), flush=True)

    for seed in args.sound:
        full("sound", seed)
    for name in [f for f in args.faults.split(",") if f]:
        with faults.planted(name):
            for seed in args.fault_seeds:
                full(f"fault:{name}", seed)
    if args.control:
        cell = harness.find_cell(args.workload, base or HERE, bench_file)
        p = cell.params
        n = harness.window_steps(p, args.seconds)
        chk = p["check_steps"]
        compared = [t for t in harness.logged_steps(n, p["log_every"])
                    if t < chk]
        for seed in args.control:
            ref = harness.reference_outputs(cell, seed, chk, n, "fp32")
            low = harness.reference_outputs(cell, seed, chk, n, "fp8")
            prog = {"losses": {t: low["losses"][t] for t in compared}}
            if p.get("ckpt_every"):
                prog.update(params=low["params"], m=low["m"])
            nums = compare.numbers(prog, ref)
            # the save's round trip and the counters are the system's own
            ok, _ = compare.judge(nums, {k: v for k, v in p["limits"].items()
                                         if k in nums})
            print(json.dumps({"kind": "control:fp8", "seed": seed,
                              "correct": ok,
                              "numbers": {k: v for k, (v, _) in nums.items()},
                              "at": {k: str(a) for k, (_, a) in nums.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
