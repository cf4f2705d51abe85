"""Faults planted in the system's timed path, to show that `correct`
catches them.  Each wraps the step function that ``train()`` builds
(``repro.train.loop.make_train_step``); nothing of the benchmark's own
runs imports this module.

  state_unchanged  the step returns the state it was given;
  half_batch       the step sees only the first half of the batch rows,
                   so the loss is the mean over the rest;
  answer_altered   one element of the updated params (the embedding's
                   first entry) is shifted by 1 where the update makes it;
  save_altered     one element of every save's host copy is shifted by 1
                   where the snapshot makes it (``serialization.
                   snapshot_to_host``, which the checkpoint manager's
                   writer thread calls on the save's device copy, or the
                   training thread where the device lacks room for one).
"""
from __future__ import annotations

from contextlib import contextmanager


def _state_unchanged(step):
    def f(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return f


def _half_batch(step):
    def f(state, batch):
        return step(state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    return f


def _answer_altered(step):
    def f(state, batch):
        new, metrics = step(state, batch)
        p = new["params"]
        emb = p["embed"]["embedding"]
        embed = dict(p["embed"], embedding=emb.at[0, 0].add(1.0))
        return dict(new, params=dict(p, embed=embed)), metrics
    return f


PLANTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}
#: faults of the save path, planted in the snapshot
SAVE_PLANTS = ("save_altered",)


def _altered_snapshot(snapshot):
    def f(tree):
        host = snapshot(tree)
        leaf = host["train"]["params"]["embed"]["embedding"]
        leaf.shards[0][1].reshape(-1)[0] += 1.0     # a HostArray's shard
        return host
    return f


@contextmanager
def planted(name: str):
    """While inside, every train() builds its step (or, for a fault of the
    save path, takes its snapshots) with fault `name`."""
    if name in SAVE_PLANTS:
        import repro.checkpoint.serialization as ser
        orig_snap = ser.snapshot_to_host
        ser.snapshot_to_host = _altered_snapshot(orig_snap)
        try:
            yield
        finally:
            ser.snapshot_to_host = orig_snap
        return
    import repro.train.loop as loop
    orig = loop.make_train_step
    wrap = PLANTS[name]

    def make(*a, **k):
        step, shardings = orig(*a, **k)
        return wrap(step), shardings

    loop.make_train_step = make
    try:
        yield
    finally:
        loop.make_train_step = orig
