"""Wall seconds per save of the background write (hash, compress, write,
gc), from the checkpoint manager's own counter (``write_s`` / ``saves``)."""


def read(run):
    st = [s for s in run.ckpt_stats if s.get("saves")]
    if not st:
        return None
    return sum(s["write_s"] for s in st) / sum(s["saves"] for s in st)
