"""Operations a training step needs, counted from the configuration's shapes.

Recomputed operations (rematerialisation), capacity padding and masked-out
attention scores are not counted: the count is what the step requires, so
a share of the chip's peak built on it cannot pass 100% by counting work
the program chose to do.

- A matrix product with N weights costs 2 N per token forward and 4 N
  backward (gradients of its input and of its weights).
- Causal attention costs, per token and layer, S H (dk + dv) forward: the
  scores and the weighted values over the S/2 earlier positions a token
  sees on average.
- A routed expert layer runs ``num_experts_per_tok`` experts per token.
"""
from __future__ import annotations

from typing import Any, Dict

import reference


def attention_params(c: Dict[str, Any]) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    if reference.is_mla(c):
        nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
        qr, kvr, dv = c["q_lora_rank"], c["kv_lora_rank"], c["v_head_dim"]
        return (d * qr + qr * h * (nope + rope) + d * (kvr + rope)
                + kvr * h * (nope + dv) + h * dv * d)
    dh, kv = reference.head_dim(c), c["num_key_value_heads"]
    return d * dh * (h + 2 * kv) + h * dh * d


def mlp_params(c: Dict[str, Any], active: bool = True) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    if not reference.is_moe(c):
        return 3 * d * f
    e = c["num_local_experts"]
    return (c["num_experts_per_tok"] if active else e) * 3 * d * f + d * e


def layer_params(c: Dict[str, Any], active: bool = True) -> int:
    return attention_params(c) + mlp_params(c, active)


def active_params(c: Dict[str, Any]) -> int:
    """Weights a token meets in matrix products: every layer's active part
    and the output head (the input embedding is a lookup)."""
    return c["num_hidden_layers"] * layer_params(c) + c["hidden_size"] * c["vocab_size"]


def attention_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward, one layer, causal."""
    h = c["num_attention_heads"]
    if reference.is_mla(c):
        dk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
        dv = c["v_head_dim"]
    else:
        dk = dv = reference.head_dim(c)
    return float(seq * h * (dk + dv))


def step_flops(c: Dict[str, Any], w: Dict[str, Any]) -> float:
    """Operations one step of workload ``w`` needs.  ``trained == "all"``:
    the forward and backward of everything.  Parts of the last layer
    (``["attn", "ln1", "ln2"]``, ...): the forward of everything, and the
    backward from the loss down to the lowest trained part of the last
    layer: the head and any frozen block above a trained one pass input
    gradients only (2 N), a trained block also its weight gradients (4 N)."""
    seq = int(w["seq"])
    tokens = float(w["batch"]) * seq
    n_layers = c["num_hidden_layers"]
    attn = attention_flops_per_token(c, seq)
    forward = 2.0 * active_params(c) + n_layers * attn
    trained = w["trained"]
    if trained == "all":
        return 3.0 * forward * tokens
    backward = 2.0 * c["hidden_size"] * c["vocab_size"]
    if "mlp" in trained or any(p in trained for p in ("attn", "ln1", "ln2")):
        backward += (4.0 if "mlp" in trained else 2.0) * mlp_params(c)
    if "attn" in trained:
        backward += 4.0 * attention_params(c) + 2.0 * attn
    elif "ln1" in trained:
        backward += 2.0 * attention_params(c) + 2.0 * attn
    return (forward + backward) * tokens
