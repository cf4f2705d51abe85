"""Fault-tolerant training loop: the paper's FSM at the jit-step level.

RUN -> (every ckpt_every steps) QUIESCE/DRAIN -> SNAPSHOT -> RESUME

  drain    = block_until_ready(state) + wait for previous async write +
             drain (or cache) the data-prefetch queue
  snapshot = TrainState pytree + pipeline cursor + rng; nothing else exists
             to save — the functional step makes the proxy boundary
             structural (DESIGN.md §2).  A device copy, fetched and copied
             to host by the writer while the next steps run; where the
             device has no room for it, the host snapshot, synchronously
  restore  = newest valid checkpoint, auto-resumed, placed with the
             step's state shardings on the current mesh.

Fresh and restored state, and every batch, are laid out with the step's
own shardings, which are also the jit's in/out shardings: on a (4, 1)
mesh the state lands on all four devices, not on the default one.
"""
from __future__ import annotations

from contextlib import ExitStack
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ArchConfig
from repro.core import trace as _trace
from repro.data.pipeline import TokenPipeline
from repro.distributed.sharding import ShardingRules, resolve_spec
from repro.models.layers import Policy
from repro.train.state import make_train_state
from repro.train.step import make_train_step


@dataclass
class TrainResult:
    losses: List[float] = field(default_factory=list)
    steps_run: int = 0
    resumed_from: Optional[int] = None
    ckpt_stats: dict = field(default_factory=dict)
    #: the final TrainState, laid out on the mesh (callers inspect its
    #: shardings; drop it to free the device memory)
    state: Any = None


def train(cfg: ArchConfig, mesh, rules: ShardingRules, *,
          n_steps: int,
          global_batch: int,
          seq_len: int,
          ckpt_root: Optional[str | Path] = None,
          ckpt_every: int = 50,
          keep: int = 3,
          base_lr: float = 3e-4,
          warmup: int = 20,
          accum_steps: int = 1,
          policy: Policy = Policy(),
          seed: int = 0,
          fail_at_step: Optional[int] = None,
          log_every: int = 10,
          remat: bool = True) -> TrainResult:
    """Run (or resume) training.  ``fail_at_step`` injects a crash for the
    fault-tolerance tests: the process raises AFTER that step completes but
    BEFORE the next checkpoint — a rerun must recover from the last one.

    Spans (``core/trace.py``, cat ``train``): ``train.startup`` from entry
    to the return of the first step's dispatch (it compiles or loads the
    step), over ``train.restore`` and ``train.init_state``; per step
    ``train.step`` (args ``step``) over ``train.batch``; ``train.log_sync``
    where a loss is read back; ``train.save`` around each save call;
    ``train.final_wait`` around the closing wait for the last write;
    ``train.teardown`` where the step program is freed."""
    with ExitStack() as starting:
        starting.enter_context(_trace.span("train.startup", cat="train"))
        step_fn, st_shard = make_train_step(
            cfg, mesh, rules, accum_steps=accum_steps, base_lr=base_lr,
            warmup=warmup, policy=policy, max_seq=seq_len,
            total_steps=n_steps, remat=remat)
        tok = NamedSharding(mesh, resolve_spec(
            ("batch", "seq"), (global_batch, seq_len), mesh, rules))
        b_shard = {"tokens": tok, "targets": tok}
        jit_step = jax.jit(step_fn, in_shardings=(st_shard, b_shard),
                           out_shardings=(st_shard, None),
                           donate_argnums=(0,))
        rep = NamedSharding(mesh, P())

        result = TrainResult()
        mgr = None
        state = None
        pipe = None
        if ckpt_root is not None:
            mgr = CheckpointManager(ckpt_root, keep=keep)
            with _trace.span("train.restore", cat="train"):
                template = jax.eval_shape(lambda: make_train_state(
                    cfg, jax.random.PRNGKey(seed), seq_len))
                template = {"train": template,
                            "data": {"seed": np.int64(0),
                                     "cursor": np.int64(0)}}
                restored, meta = mgr.restore(
                    template, {"train": st_shard,
                               "data": {"seed": rep, "cursor": rep}})
            if restored is not None:
                state = restored["train"]
                pipe = TokenPipeline(cfg.vocab_size, global_batch, seq_len,
                                     seed=int(restored["data"]["seed"]))
                pipe.cursor = int(restored["data"]["cursor"])
                result.resumed_from = int(meta.get("step", -1))
        if state is None:
            with _trace.span("train.init_state", cat="train"):
                state = jax.device_put(make_train_state(
                    cfg, jax.random.PRNGKey(seed), seq_len), st_shard)
                pipe = TokenPipeline(cfg.vocab_size, global_batch, seq_len,
                                     seed=seed)

        start_step = int(state["step"])
        for step in range(start_step, n_steps):
            with _trace.span("train.step", cat="train",
                             args={"step": step}):
                with _trace.span("train.batch", cat="train"):
                    batch = pipe.next_batch()
                    batch = {k: jax.device_put(v, b_shard[k])
                             for k, v in batch.items()}
                state, metrics = jit_step(state, batch)
            starting.close()            # start-up ends at the first step
            if step % log_every == 0 or step == n_steps - 1:
                with _trace.span("train.log_sync", cat="train"):
                    loss = float(metrics["loss"])
                result.losses.append(loss)
            result.steps_run += 1
            if mgr is not None and (step + 1) % ckpt_every == 0:
                payload = {"train": state,
                           "data": {"seed": np.int64(pipe.seed),
                                    "cursor": np.int64(pipe.cursor)}}
                with _trace.span("train.save", cat="train",
                                 args={"step": step + 1}):
                    mgr.save(step + 1, payload,
                             meta={"step": step + 1, "arch": cfg.name,
                                   "rules": rules.name,
                                   "mesh": dict(mesh.shape)})
            if fail_at_step is not None and step + 1 >= fail_at_step:
                if mgr is not None:
                    mgr.wait()
                raise RuntimeError(f"injected failure after step {step + 1}")
    if mgr is not None:
        with _trace.span("train.final_wait", cat="train"):
            mgr.wait()
        result.ckpt_stats = dict(mgr.stats)
    with _trace.span("train.teardown", cat="train"):
        # freeing the step frees its compiled program, which takes
        # milliseconds: do it here, inside a span, not unseen at return
        del jit_step, step_fn
    result.state = state
    return result
