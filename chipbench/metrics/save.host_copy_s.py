"""Seconds per save of the host-side copy inside the snapshot (``.copy()``
of each transferred shard), from the checkpoint manager's own counter
(``snapshot_copy_s`` / ``saves``).

Read only beside the window's device trace, whose idle gaps it splits; a
run with no device planes (the CPU) leaves it out."""


def read(run):
    st = [s for s in run.ckpt_stats
          if s.get("saves") and "snapshot_copy_s" in s]
    if not run.trace or not st:
        return None
    return sum(s["snapshot_copy_s"] for s in st) / sum(s["saves"] for s in st)
