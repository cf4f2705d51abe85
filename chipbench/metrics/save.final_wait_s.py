"""Seconds the window's ``train()`` call waits, after its last step, for
its last write to finish: the program's ``train.final_wait`` span.

Read only beside the window's device trace, whose idle gaps it splits; a
run with no device planes (the CPU) leaves it out."""


def read(run):
    if not run.trace:
        return None
    waits = [d for name, _, d in run.program_spans
             if name == "train.final_wait"]
    return waits[-1] if waits else None
