"""The float32 reference against the system's own step at a tiny
Llama-style size on the CPU, with the system computing in float32 too:
the same seeded weights, loss, gradients (as Adam's first moment after
one step) and updated params."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, traffic_gen
from chipbench.reference import llama
from chipbench.tests.tiny import TINY

OPT = {"base_lr": 3e-4, "warmup": 20, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
       "weight_decay": 0.1, "clip_norm": 1.0, "min_lr_frac": 0.1}
SEED = 7


def _program(cfg_dict):
    import dataclasses
    from repro.configs.base import ArchConfig
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(**{k: v for k, v in cfg_dict.items() if k in names})


@pytest.mark.parametrize("tied", [False, True])
def test_seeded_init_matches_program(tied):
    from repro.train.state import make_train_state
    cfg = dict(TINY, tie_embeddings=tied)
    prog = compare.flat_names(
        make_train_state(_program(cfg), jax.random.PRNGKey(SEED), 32)
        ["params"])
    ref = llama.init_params(cfg, SEED)
    assert sorted(prog) == sorted(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(prog[k]), np.asarray(ref[k]),
                                      err_msg=k)


@pytest.mark.parametrize("tied", [False, True])
def test_one_step_matches_program_in_float32(tied):
    from repro.distributed.sharding import make_variant
    from repro.launch.mesh import make_local_mesh
    from repro.models.layers import Policy
    from repro.train.state import make_train_state
    from repro.train.step import make_train_step
    cfg = dict(TINY, tie_embeddings=tied)
    pcfg = _program(cfg)
    tok, tgt = traffic_gen.batch(cfg["vocab_size"], 4, 32, SEED, 0)
    step, _ = make_train_step(pcfg, make_local_mesh(n=1),
                              make_variant("baseline"), base_lr=3e-4,
                              warmup=20, total_steps=10, max_seq=32,
                              policy=Policy(compute=jnp.float32))
    state = make_train_state(pcfg, jax.random.PRNGKey(SEED), 32)
    new, metrics = jax.jit(step)(state, {"tokens": jnp.asarray(tok),
                                         "targets": jnp.asarray(tgt)})
    ref = llama.run(cfg, OPT, SEED, [(tok, tgt)], 1, 10)
    assert float(metrics["loss"]) == pytest.approx(ref["losses"][0],
                                                   rel=1e-5)
    m = compare.flat_names(new["opt"]["m"])
    p = compare.flat_names(new["params"])
    for k in ref["m"]:
        scale = np.abs(ref["m"][k]).max()
        np.testing.assert_allclose(np.asarray(m[k]), ref["m"][k],
                                   atol=1e-4 * scale, err_msg=k)
        np.testing.assert_allclose(np.asarray(p[k]), ref["params"][k],
                                   rtol=1e-5, atol=1e-7, err_msg=k)


@pytest.mark.parametrize("layers,want", [(None, 31_852_224),
                                         (30, 134_515_008)])
def test_param_count_matches_program(layers, want):
    """The configuration as run (one layer), and at the published depth."""
    import json
    from chipbench.harness import HERE
    cfg = json.loads((HERE / "configs" / "smollm-135m-1l.json").read_text())
    if layers is not None:
        cfg = dict(cfg, n_layers=layers)
    assert llama.n_params(cfg) == want
    assert _program(cfg).n_params() == want


def test_fp8_control_rounds_operands_and_cotangents():
    x = jnp.linspace(-3.0, 3.0, 64).reshape(8, 8)
    q = llama._q8(x)
    assert float(jnp.max(jnp.abs(q - x))) > 0
    assert float(jnp.max(jnp.abs(q - x) / 3.0)) < 2 ** -3
    f = lambda a: jnp.sum(llama._einsum_fp8("ij,jk->ik", a, x) ** 2)
    g = jax.grad(f)(x)
    g32 = jax.grad(lambda a: jnp.sum((a @ x) ** 2))(x)
    err = float(jnp.linalg.norm(g - g32) / jnp.linalg.norm(g32))
    assert 1e-4 < err < 0.2
