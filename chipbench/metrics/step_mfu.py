"""Model FLOPs of the train steps in the window over the train-step
program's device time, as a share of the chip's bf16 peak.

The step program is the one that took the most device time in the trace;
its executions must match the window's steps.  FLOPs come from
``chipbench.flops``, by block kind (no rematerialised work counted).
Where that module cannot count the configuration's stack
(``run.flops_per_step`` is None) this reads nothing."""


def read(run):
    if run.flops_per_step is None or not run.trace \
            or not run.trace["modules"]:
        return None
    name, (count, seconds) = max(run.trace["modules"].items(),
                                 key=lambda kv: kv[1][1])
    if count != run.steps or seconds <= 0:
        return None
    return 100.0 * run.flops_per_step * count / seconds \
        / run.peaks["bf16_flops_per_s"]
