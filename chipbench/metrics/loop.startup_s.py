"""Seconds from the window's ``train()`` entry to the return of its first
step's dispatch (state init or restore probe, building the step, lowering
and the compile-cache load): the program's ``train.startup`` span.

Read only beside the window's device trace, whose idle gaps it splits; a
run with no device planes (the CPU) leaves it out."""


def read(run):
    if not run.trace:
        return None
    starts = [d for name, _, d in run.program_spans
              if name == "train.startup"]
    return starts[-1] if starts else None
