"""Share of the train window in which no operation ran on the device:
1 - union of device-op intervals / traced window."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * run.trace["idle_share"]
