"""Elastic scaling: adapt mesh + shardings to whatever devices exist now,
and restore any checkpoint onto them (cross-topology restart).

The admin-log idea from the paper appears here as the mesh-reconstruction
record: a checkpoint's manifest stores (mesh shape, axis names, rules name)
as *informational* metadata; restore ignores it and rebuilds for the
CURRENT world — the whole point of the proxy boundary.

``atomic_reshape`` is the single reshape entry point: BOTH layers — the
jax-mesh tensor state (``elastic_restore`` + CheckpointManager) and the
rank world (``MPIJob.restart``) — move to the new world shape under ONE
``Membership.bump``, so their epoch numbers can never diverge (two
independent bumps would let a zombie of the old rank world stamp messages
that the tensor layer's generation still accepts)."""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional, Sequence, Tuple

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.distributed.sharding import ShardingRules, make_variant
from repro.launch.mesh import make_mesh


def choose_mesh(n_devices: Optional[int] = None,
                model_parallel: int = 1):
    """Largest (data, model) mesh for the current world size."""
    n = n_devices or len(jax.devices())
    model = model_parallel
    while n % model:
        model -= 1
    return make_mesh((n // model, model), ("data", "model"))


def elastic_restore(mgr: CheckpointManager, template, mesh,
                    rules: ShardingRules, state_shardings=None):
    """Restore the newest valid checkpoint onto the CURRENT mesh (layouts
    derived from mesh+rules when `state_shardings` is not given).  Returns
    (state, meta) — meta reports the topology change: the SOURCE world the
    manifest recorded, the world restored onto, whether they differ, and
    the membership generation the checkpoint was written in."""
    # explicit shardings win; otherwise layouts derive from mesh+rules
    state, meta = mgr.restore(template, state_shardings, mesh=mesh,
                              rules=rules)
    if state is None:
        return None, None
    meta = dict(meta or {})
    now = {"devices": len(mesh.devices.flatten()), "mesh": dict(mesh.shape)}
    source = meta.get("world")
    meta["restored_onto"] = now
    meta["source_world"] = source
    meta["generation"] = meta.get("generation", 0)
    meta["topology_changed"] = bool(
        source and source.get("n_devices") not in (None, now["devices"]))
    return state, meta


@dataclass
class ReshapeReport:
    """What one atomic reshape did: the single post-bump generation, the
    adopted world size, and whichever layers were restored."""
    generation: int
    world_size: int
    dead_ranks: Tuple[int, ...]
    state: Any = None            # jax-mesh tensor state (mgr layer), or None
    meta: Optional[dict] = None  # elastic_restore's topology report
    job: Any = None              # reshaped MPIJob (rank-world layer), or None
    layers: Tuple[str, ...] = field(default=())


def atomic_reshape(membership, dead: Sequence[int] = (),
                   world_size: Optional[int] = None,
                   *,
                   mgr: Optional[CheckpointManager] = None,
                   template=None, mesh=None,
                   rules: Optional[ShardingRules] = None,
                   state_shardings=None,
                   ckpt_dir: Optional[str | Path] = None,
                   step_fn=None, init_fn=None, transport: str = "shm",
                   ckpt_store=None, heartbeat_timeout: float = 5.0,
                   coord_timeout: float = 60.0) -> ReshapeReport:
    """One reshape, one generation bump, every layer (DESIGN.md §8).

    Bumps `membership` past `dead` to `world_size` exactly once, then
    restores whichever layers the caller drives onto the NEW epoch:

      * tensor layer — pass `mgr` (+ `template`/`mesh`/`rules` as
        ``elastic_restore`` takes them): the manager's stamped generation
        is set to the bumped epoch before the restore, so the next
        manifest it writes records the same generation the rank world
        rejects stale messages against;
      * rank world — pass `ckpt_dir` (+ `step_fn`/`init_fn`/...):
        ``MPIJob.restart`` reshapes the world with THIS membership, whose
        bump already happened here — the job performs none of its own.

    Either layer alone is fine; passing both is the lockstep case the
    name promises.  Returns a ``ReshapeReport``."""
    dead = tuple(sorted({int(r) for r in dead}))
    gen = membership.bump(dead, world_size=world_size)
    report = ReshapeReport(generation=gen,
                           world_size=membership.world_size,
                           dead_ranks=dead)
    layers = []
    if mgr is not None:
        mgr.generation = gen
        report.state, report.meta = elastic_restore(
            mgr, template, mesh, rules, state_shardings)
        layers.append("mesh")
    if ckpt_dir is not None:
        from repro.core.runtime import MPIJob
        report.job = MPIJob.restart(
            ckpt_dir, step_fn, init_fn, transport=transport,
            world_size=membership.world_size, dead_ranks=dead,
            membership=membership, heartbeat_timeout=heartbeat_timeout,
            coord_timeout=coord_timeout, ckpt_store=ckpt_store)
        layers.append("world")
    report.layers = tuple(layers)
    return report
