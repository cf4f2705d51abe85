"""Model FLOPs of one training step of a decoder, from a configuration
file's sizes, counted by block kind.

Counted: every matrix product of the forward pass at 2 FLOPs per
multiply-add -- the attention and feed-forward projections, the router,
the output head, and the attention scores and their weighted sum over the
full S x S square (the masked half included, as the usual MFU convention
does) -- and the backward pass at twice the forward.  Not counted: the
embedding gather, norms, softmax and elementwise work, the optimizer, and
anything the program recomputes (rematerialisation), which is what makes
this a model FLOP count and not a hardware one.

Per token and layer:

  attention     GQA: q and o d*h*hd each, k and v d*kv*hd each.  Latent
                attention (``mla``): q d*h*(nope+rope), the down
                projection d*(kv_lora+rope), the up projections
                kv_lora*h*(nope+v), the output h*v*d.
  scores        per sequence and layer 2*S^2*h*qk plus 2*S^2*h*v for the
                weighted sum (qk = v = hd under GQA; nope+rope and v
                under ``mla``).
  feed-forward  a gated MLP (swiglu, geglu) 3*d*width, a plain one
                2*d*width; width d_ff, or ``moe.dense_ff`` in the first
                ``moe.first_k_dense`` layers of a model with experts.
                An expert layer: the router d*R, the routed experts
                top_k * (n_routed / R) MLPs of width d_expert, the shared
                experts one MLP of width n_shared*d_expert, and the shared
                gate d where ``shared_gate`` is set.
  head          d * vocab_size, the slice of the vocabulary the file holds.

R is the number of experts the router scores: ``moe.n_routed_total``
where the file gives it, else ``moe.n_routed``.  This is the convention
for a chip's share of an expert layer (a configuration that holds
``n_routed`` of ``n_routed_total`` experts, the router at its published
width): the chip is charged the expected share of the routed work, the
tokens routed to the experts it holds.

A stack with other blocks than attention (``block_pattern``,
``pattern_tail``), an encoder or vision tokens is not counted:
``matmul_params`` and ``train_step_flops`` give None, not a guess.
"""
from __future__ import annotations

from typing import Optional


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def _counted(cfg: dict) -> bool:
    """Whether every block of `cfg` is of a kind this module counts."""
    kinds = list(cfg.get("block_pattern") or ()) \
        + list(cfg.get("pattern_tail") or ())
    return (all(k == "attn" for k in kinds) and not cfg.get("encoder")
            and not cfg.get("n_vision_tokens"))


def _qk_v(cfg: dict) -> tuple:
    """Widths of a head's query-key product and of its value."""
    m = cfg.get("mla")
    if m:
        return m["qk_nope_head_dim"] + m["qk_rope_head_dim"], \
            m["v_head_dim"]
    return head_dim(cfg), head_dim(cfg)


def _attention_params(cfg: dict) -> int:
    d, h = cfg["d_model"], cfg["n_heads"]
    m = cfg.get("mla")
    if m:
        nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                         m["v_head_dim"])
        return (d * h * (nope + rope) + d * (m["kv_lora_rank"] + rope)
                + m["kv_lora_rank"] * h * (nope + v) + h * v * d)
    hd = head_dim(cfg)
    return 2 * d * h * hd + 2 * d * cfg["n_kv_heads"] * hd


def _mlp(cfg: dict, width: int) -> int:
    gated = cfg.get("mlp", "swiglu") in ("swiglu", "geglu")
    return (3 if gated else 2) * cfg["d_model"] * width


def _ffn_params(cfg: dict) -> float:
    """Feed-forward weights per token, summed over the layers; a float
    where there are experts, whose routed part is an expected share."""
    e = cfg.get("moe")
    if not e:
        return cfg["n_layers"] * _mlp(cfg, cfg["d_ff"])
    d = cfg["d_model"]
    scored = e.get("n_routed_total") or e["n_routed"]
    n_dense = e.get("first_k_dense", 0)
    dense = n_dense * _mlp(cfg, e.get("dense_ff") or cfg["d_ff"])
    routed = e["top_k"] * e["n_routed"] * _mlp(cfg, e["d_expert"]) / scored
    expert = (d * scored + routed
              + _mlp(cfg, e.get("n_shared", 0) * e["d_expert"])
              + (d if e.get("shared_gate") else 0))
    return dense + (cfg["n_layers"] - n_dense) * expert


def matmul_params(cfg: dict) -> Optional[float]:
    """Weights that take part in a matrix product, per token; None where
    the stack is not counted (see the module's docstring)."""
    if not _counted(cfg):
        return None
    return (cfg["n_layers"] * _attention_params(cfg) + _ffn_params(cfg)
            + cfg["d_model"] * cfg["vocab_size"])


def train_step_flops(cfg: dict, batch: int, seq: int) -> Optional[dict]:
    """{"dense", "attention", "total"} FLOPs of one step (forward and
    backward) over `batch` rows of `seq` tokens; None where the stack is
    not counted."""
    params = matmul_params(cfg)
    if params is None:
        return None
    qk, v = _qk_v(cfg)
    dense = 6 * params * batch * seq
    attention = 3 * cfg["n_layers"] * 2 * batch * seq * seq \
        * cfg["n_heads"] * (qk + v)
    return {"dense": dense, "attention": attention,
            "total": dense + attention}
