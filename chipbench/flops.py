"""Model FLOPs of one training step of a Llama-style decoder, from shapes.

Counted: every matrix product of the forward pass at 2 FLOPs per
multiply-add -- the attention and MLP projections, the output head, and
the attention scores and their weighted sum over the full S x S square
(the masked half included, as the usual MFU convention does) -- and the
backward pass at twice the forward.  Not counted: the embedding gather,
norms, softmax and elementwise work, the optimizer, and anything the
program recomputes (rematerialisation), which is what makes this a model
FLOP count and not a hardware one.
"""
from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product, per token."""
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        head_dim(cfg)
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * cfg["d_ff"]
    return cfg["n_layers"] * per_layer + d * cfg["vocab_size"]


def train_step_flops(cfg: dict, batch: int, seq: int) -> dict:
    """{"dense", "attention", "total"} FLOPs of one step (forward and
    backward) over `batch` rows of `seq` tokens."""
    tokens = batch * seq
    dense = 6 * matmul_params(cfg) * tokens
    attention = 3 * cfg["n_layers"] * 4 * batch * seq * seq \
        * cfg["n_heads"] * head_dim(cfg)
    return {"dense": dense, "attention": attention,
            "total": dense + attention}
