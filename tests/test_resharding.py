"""Cross-topology restore (paper §7 at tensor level): checkpoints written
under one mesh restore onto another.  Multi-device cases run in
subprocesses with their own XLA device-count flags (smoke tests in this
process must keep seeing ONE device)."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.checkpoint.resharding import restore_resharded

_SAVE_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import sys, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager

from repro.launch.mesh import make_mesh
mesh = make_mesh({mesh_shape}, {mesh_axes})
w = jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8)
w = jax.device_put(w, NamedSharding(mesh, P({spec})))
b = jnp.arange(8, dtype=jnp.bfloat16)
mgr = CheckpointManager(r"{root}")
mgr.save(1, {{"w": w, "b": b}}, meta={{"mesh": str(dict(mesh.shape))}})
mgr.wait()
man = json.load(open(r"{root}/step_0000000001/MANIFEST.json"))
print(json.dumps({{"n_shards_w": len(man["leaves"]["w"]["shards"])}}))
"""

_LOAD_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager

from repro.launch.mesh import make_mesh
mesh = make_mesh({mesh_shape}, {mesh_axes})
tpl = {{"w": jax.ShapeDtypeStruct((16, 8), jnp.float32),
       "b": jax.ShapeDtypeStruct((8,), jnp.bfloat16)}}
sh = {{"w": NamedSharding(mesh, P({spec})), "b": NamedSharding(mesh, P())}}
mgr = CheckpointManager(r"{root}")
out, meta = mgr.restore(tpl, sh)
ok_w = bool(np.array_equal(np.asarray(out["w"]),
            np.arange(16 * 8, dtype=np.float32).reshape(16, 8)))
ok_b = bool(np.array_equal(np.asarray(out["b"], np.float32),
            np.arange(8, dtype=np.float32)))
print(json.dumps({{"ok": ok_w and ok_b,
                   "shards": len(out["w"].addressable_shards)}}))
"""


def _run(snippet: str) -> dict:
    r = subprocess.run([sys.executable, "-c", snippet], capture_output=True,
                       text=True, timeout=300,
                       env={**__import__("os").environ, "PYTHONPATH": "src"},
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_cross_mesh_restore_2x4_to_8(tmp_path):
    """Save sharded over a (2,4) mesh; restore onto (8,) and (1,1)."""
    save = _SAVE_SNIPPET.format(ndev=8, mesh_shape="(2, 4)",
                                mesh_axes='("data", "model")', nax=2,
                                spec='"data", "model"', root=tmp_path)
    info = _run(save)
    assert info["n_shards_w"] == 8       # 2x4 distinct index windows

    load = _LOAD_SNIPPET.format(ndev=8, mesh_shape="(8,)",
                                mesh_axes='("data",)', nax=1,
                                spec='"data"', root=tmp_path)
    out = _run(load)
    assert out["ok"] and out["shards"] == 8

    load1 = _LOAD_SNIPPET.format(ndev=1, mesh_shape="(1, 1)",
                                 mesh_axes='("data", "model")', nax=2,
                                 spec='"data", "model"', root=tmp_path)
    out1 = _run(load1)
    assert out1["ok"]


@pytest.mark.slow
def test_cross_mesh_restore_4_to_2x2(tmp_path):
    save = _SAVE_SNIPPET.format(ndev=4, mesh_shape="(4,)",
                                mesh_axes='("data",)', nax=1,
                                spec='"data"', root=tmp_path)
    _run(save)
    load = _LOAD_SNIPPET.format(ndev=4, mesh_shape="(2, 2)",
                                mesh_axes='("data", "model")', nax=2,
                                spec='"model", "data"', root=tmp_path)
    out = _run(load)
    assert out["ok"]


def test_single_device_roundtrip_with_new_sharding(tmp_path):
    """Degenerate path in-process: restore with explicit default sharding."""
    mgr = CheckpointManager(tmp_path)
    st = {"w": jnp.arange(12.0).reshape(3, 4)}
    mgr.save(1, st)
    mgr.wait()
    tpl = {"w": jax.ShapeDtypeStruct((3, 4), jnp.float32)}
    out = restore_resharded(mgr.latest_valid(), tpl, None)
    assert np.array_equal(np.asarray(out["w"]), np.asarray(st["w"]))
