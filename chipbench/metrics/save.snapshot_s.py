"""Seconds per save of the synchronous device-to-host copy, from the
checkpoint manager's own counter (``snapshot_s`` / ``saves``)."""


def read(run):
    st = [s for s in run.ckpt_stats if s.get("saves")]
    if not st:
        return None
    return sum(s["snapshot_s"] for s in st) / sum(s["saves"] for s in st)
