"""Plain float32 reference of a Llama-style decoder and of its training step.

It follows the published architecture: token embedding, then per layer
RMSNorm -> grouped-query attention with NeoX-style (half-split) rotary
embeddings and a causal softmax -> residual, RMSNorm -> SwiGLU MLP
(``silu(x @ wg) * (x @ wi) @ wo``) -> residual, a final RMSNorm and the
output head (the transposed embedding when embeddings are tied).  The
loss is the mean token cross-entropy.  The optimizer is AdamW with
global-norm clipping, decoupled weight decay on every leaf, and a linear
warm-up then cosine learning rate.  Every matrix product runs at
``Precision.HIGHEST``, so a TPU computes it in float32.

It imports nothing of the system under test.  It makes the same seeded
weights from the seed by the same recipe (one ``jax.random.split`` of
``PRNGKey(seed)`` into a key per leaf, leaves in sorted-name order, each
normal with std ``1/sqrt(shape[-2])``), and takes batches from
``chipbench.traffic_gen``.

Departures from the published models, all shared with the system under
test: no biases, RMSNorm epsilon from the configuration, and the init
recipe above (the published checkpoints are not used).

``precision="fp8"`` is the control: every matrix product takes its
operands (and, in the backward pass, its cotangent) rounded to float8
e4m3 with a per-tensor scale, accumulating in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def param_layout(cfg: dict):
    """[(name, shape, init)] in the order the seeded init walks the leaves
    (sorted names; stacked layers carry a leading layer axis)."""
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        head_dim(cfg)
    f, v, n = cfg["d_ff"], cfg["vocab_size"], cfg["n_layers"]
    out = [("embed/embedding", (v, d), "normal")]
    if not cfg.get("tie_embeddings", False):
        out.append(("embed/lm_head", (d, v), "normal"))
    out.append(("final/scale", (d,), "ones"))
    blk = [("attn/wk", (d, kv, hd), "normal"),
           ("attn/wo", (h, hd, d), "normal"),
           ("attn/wq", (d, h, hd), "normal"),
           ("attn/wv", (d, kv, hd), "normal"),
           ("ln1/scale", (d,), "ones"),
           ("ln2/scale", (d,), "ones"),
           ("mlp/wg", (d, f), "normal"),
           ("mlp/wi", (d, f), "normal"),
           ("mlp/wo", (f, d), "normal")]
    out += [(f"units/b0/{k}", (n,) + s, i) for k, s, i in blk]
    return out


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for _, s, _ in param_layout(cfg))


def init_params(cfg: dict, seed: int) -> dict:
    """{name: float32 array} on the default device."""
    layout = param_layout(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(layout))
    out = {}
    for (name, shape, init), key in zip(layout, keys):
        if init == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            out[name] = jax.random.normal(key, shape, jnp.float32) \
                * (1.0 / np.sqrt(max(fan_in, 1)))
    return out


# --------------------------------------------------------------------------
# Matrix products at the reference's precision, or the fp8 control
# --------------------------------------------------------------------------

def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale, back to float32."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return jnp.einsum(spec, _q8(a), _q8(b), precision=HIGHEST)


def _einsum_fp8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _einsum_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(_q8(g))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def _qbf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_bf16(spec, a, b):
    return jnp.einsum(spec, _qbf16(a), _qbf16(b), precision=HIGHEST)


def _einsum_bf16_fwd(spec, a, b):
    qa, qb = _qbf16(a), _qbf16(b)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _einsum_bf16_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(_qbf16(g))


_einsum_bf16.defvjp(_einsum_bf16_fwd, _einsum_bf16_bwd)


def _einsum(precision: str):
    if precision == "fp32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _einsum_fp8
    if precision == "bf16":
        return _einsum_bf16
    raise ValueError(f"unknown reference precision {precision!r}")


# --------------------------------------------------------------------------
# Forward and loss
# --------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """NeoX half-split rotary over the whole head dim.  x (B,S,H,hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs     # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, ein, x, p):
    eps = cfg.get("norm_eps", 1e-5)
    h_, kv = cfg["n_heads"], cfg["n_kv_heads"]
    hd = head_dim(cfg)
    s = x.shape[1]
    h = _rms(x, p["ln1/scale"], eps)
    q = _rope(ein("bsd,dhk->bshk", h, p["attn/wq"]), cfg["rope_theta"])
    k = _rope(ein("bsd,dhk->bshk", h, p["attn/wk"]), cfg["rope_theta"])
    v = ein("bsd,dhk->bshk", h, p["attn/wv"])
    k = jnp.repeat(k, h_ // kv, axis=2)
    v = jnp.repeat(v, h_ // kv, axis=2)
    sc = ein("bqhd,bkhd->bhqk", q, k) * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = ein("bhqk,bkhd->bqhd", pr, v)
    x = x + ein("bshk,hkd->bsd", o, p["attn/wo"])
    h = _rms(x, p["ln2/scale"], eps)
    m = jax.nn.silu(ein("bsd,df->bsf", h, p["mlp/wg"])) \
        * ein("bsd,df->bsf", h, p["mlp/wi"])
    return x + ein("bsf,fd->bsd", m, p["mlp/wo"])


def loss_fn(cfg: dict, params: dict, tokens, targets,
            precision: str = "fp32"):
    """Mean token cross-entropy over the rows given."""
    ein = _einsum(precision)
    x = jnp.take(params["embed/embedding"], tokens, axis=0)
    stacked = {k[len("units/b0/"):]: v for k, v in params.items()
               if k.startswith("units/b0/")}
    layer = jax.checkpoint(functools.partial(_layer, cfg, ein))

    def body(x, p):
        return layer(x, p), None

    x, _ = jax.lax.scan(body, x, stacked)
    x = _rms(x, params["final/scale"], cfg.get("norm_eps", 1e-5))
    if cfg.get("tie_embeddings", False):
        logits = ein("bsd,vd->bsv", x, params["embed/embedding"])
    else:
        logits = ein("bsd,dv->bsv", x, params["embed/lm_head"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def block_rows(cfg: dict, batch: int, seq: int,
               budget_bytes: float = 2e9) -> int:
    """Rows per block so that one block's logits and attention scores
    stay under `budget_bytes` each; divides `batch`."""
    per_row = 4 * seq * max(cfg["vocab_size"], cfg["n_heads"] * seq)
    r = max(1, min(batch, int(budget_bytes // per_row)))
    while batch % r:
        r -= 1
    return r


# --------------------------------------------------------------------------
# Training step
# --------------------------------------------------------------------------

def lr_at(step: int, opt: dict, total_steps: int) -> float:
    """Linear warm-up from base/warmup, then cosine to min_lr_frac."""
    base, warm = opt["base_lr"], opt["warmup"]
    if step < warm:
        return base * min((step + 1.0) / max(warm, 1), 1.0)
    prog = min(max((step - warm) / max(total_steps - warm, 1), 0.0), 1.0)
    mf = opt["min_lr_frac"]
    return base * (mf + (1 - mf) * 0.5 * (1 + math.cos(math.pi * prog)))


def make_step(cfg: dict, opt: dict, rows: int, precision: str = "fp32"):
    """Returns step(params, m, v, count, lr, tokens, targets) ->
    (params, m, v, loss, grads); gradients are accumulated over blocks of
    `rows` rows, each block one call of a jitted function."""
    grad_block = jax.jit(jax.value_and_grad(
        lambda p, t, y: loss_fn(cfg, p, t, y, precision)))
    acc = jax.jit(lambda a, g, w: jax.tree.map(lambda x, y: x + w * y, a, g),
                  donate_argnums=(0,))

    @jax.jit
    def update(params, grads, m, v, count, lr):
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
        scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-12))
        b1, b2 = opt["b1"], opt["b2"]
        b1c = 1.0 - b1 ** count
        b2c = 1.0 - b2 ** count
        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            g = grads[k] * scale
            new_m[k] = b1 * m[k] + (1 - b1) * g
            new_v[k] = b2 * v[k] + (1 - b2) * g * g
            upd = (new_m[k] / b1c) / (jnp.sqrt(new_v[k] / b2c) + opt["eps"])
            new_p[k] = params[k] - lr * (upd + opt["weight_decay"] * params[k])
        return new_p, new_m, new_v

    def step(params, m, v, count, lr, tokens, targets):
        b = tokens.shape[0]
        grads, loss = None, 0.0
        for i in range(0, b, rows):
            tb = tokens[i:i + rows]
            l, g = grad_block(params, tb, targets[i:i + rows])
            w = tb.shape[0] / b
            loss += float(l) * w
            grads = (jax.tree.map(lambda x: w * x, g) if grads is None
                     else acc(grads, g, w))
        new_p, new_m, new_v = update(params, grads, m, v,
                                     jnp.float32(count), jnp.float32(lr))
        return new_p, new_m, new_v, loss, grads

    return step


def leaf_norms(tree: dict) -> dict:
    """{name: float64 L2 norm} computed on the device, read to the host."""
    sq = jax.jit(lambda t: {k: jnp.sum(jnp.square(x)) for k, x in t.items()})
    return {k: math.sqrt(float(v)) for k, v in sq(tree).items()}


def run(cfg: dict, opt: dict, seed: int, batches, n_steps: int,
        total_steps: int, precision: str = "fp32",
        rows: int = 0) -> dict:
    """Train `n_steps` from the seeded init on `batches` (a list of
    (tokens, targets) numpy pairs).  Returns host numbers: the loss of
    each step, the first gradient's leaf norms, and the final params,
    first moments and initial params as float32 numpy arrays."""
    with jax.default_matmul_precision("highest"):
        params = init_params(cfg, seed)
        p0 = {k: np.asarray(x) for k, x in params.items()}
        m = {k: jnp.zeros_like(x) for k, x in params.items()}
        v = {k: jnp.zeros_like(x) for k, x in params.items()}
        b, s = batches[0][0].shape
        step = make_step(cfg, opt, rows or block_rows(cfg, b, s), precision)
        losses, g1 = [], None
        for t in range(n_steps):
            tok, tgt = batches[t]
            params, m, v, loss, grads = step(
                params, m, v, t + 1, lr_at(t, opt, total_steps),
                jnp.asarray(tok), jnp.asarray(tgt))
            losses.append(loss)
            if t == 0:
                g1 = leaf_norms(grads)
            del grads
        out = {"losses": losses, "grad1_norms": g1, "p0": p0,
               "params": {k: np.asarray(x) for k, x in params.items()},
               "m": {k: np.asarray(x) for k, x in m.items()}}
        del params, m, v
    return out
