"""Reference family ``decoder``: a stack of identical decoder layers.

Each layer is attention, grouped-query (``gqa``) or latent (``mla``,
where the file has ``kv_lora_rank``), and then a dense ``mlp`` or, where
the file has ``num_local_experts``, a capacity-dropping routed ``moe``.
It follows the model *as the configuration file states it is run* (the
keys of ``configs/<name>.json``), including the places where that differs
from the published model (the file's ``departures`` say which).

- ``init_params`` makes the weights from a key in one jitted call, in the
  tree layout the program consumes (layers stacked on axis 0 under
  ``groups[0]``), so the program and the reference start from the same
  numbers and neither takes anything from the other.
- ``loss`` is next-token cross entropy plus the router's balance term.
- ``trained_part``, ``split_last`` and ``join_last`` cut that tree for a
  fine-tune of the last layer.
- ``arch_config`` is the program's ``ArchConfig`` for the file.
- ``step_flops`` counts the operations a training step needs.

The reference functions import nothing of the program; ``arch_config``
imports it inside the function.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from reference import Dot, _dense, causal_attention, exact_dot, rmsnorm, rope


# --------------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------------- #


def head_dim(c: Dict[str, Any]) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def is_mla(c: Dict[str, Any]) -> bool:
    return c.get("kv_lora_rank") is not None


def is_moe(c: Dict[str, Any]) -> bool:
    return bool(c.get("num_local_experts"))


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #


def _layer_params(key, c: Dict[str, Any]) -> Dict[str, Any]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    ks = iter(jax.random.split(key, 16))
    layer: Dict[str, Any] = {"ln1": {"scale": jnp.ones((d,), jnp.float32)},
                             "ln2": {"scale": jnp.ones((d,), jnp.float32)}}
    if is_mla(c):
        nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
        qr, kvr, dv = c["q_lora_rank"], c["kv_lora_rank"], c["v_head_dim"]
        layer["attn"] = {
            "w_dq": _dense(next(ks), d, (d, qr)),
            "w_uq": _dense(next(ks), qr, (qr, h * (nope + rope))),
            "w_dkv": _dense(next(ks), d, (d, kvr)),
            "w_kr": _dense(next(ks), d, (d, rope)),
            "w_uk": _dense(next(ks), kvr, (kvr, h * nope)),
            "w_uv": _dense(next(ks), kvr, (kvr, h * dv)),
            "wo": _dense(next(ks), h * dv, (h * dv, d)),
        }
    else:
        dh, kv = head_dim(c), c["num_key_value_heads"]
        layer["attn"] = {
            "wq": _dense(next(ks), d, (d, h * dh)),
            "wk": _dense(next(ks), d, (d, kv * dh)),
            "wv": _dense(next(ks), d, (d, kv * dh)),
            "wo": _dense(next(ks), h * dh, (h * dh, d)),
        }
    f = c["intermediate_size"]
    if is_moe(c):
        e = c["num_local_experts"]
        layer["moe"] = {
            "router": _dense(next(ks), d, (d, e)),
            "moe_w_gate": _dense(next(ks), d, (e, d, f)),
            "moe_w_up": _dense(next(ks), d, (e, d, f)),
            "moe_w_down": _dense(next(ks), f, (e, f, d)),
        }
    else:
        layer["mlp"] = {
            "w_gate": _dense(next(ks), d, (d, f)),
            "w_up": _dense(next(ks), d, (d, f)),
            "w_down": _dense(next(ks), f, (f, d)),
        }
    return layer


def _init(key, c: Dict[str, Any]) -> Dict[str, Any]:
    d, v = c["hidden_size"], c["vocab_size"]
    k_layers, k_embed, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, c["num_hidden_layers"])
    params = {
        "embed": jax.random.normal(k_embed, (v, d), jnp.float32) * 0.02,
        "groups": [jax.vmap(lambda k: _layer_params(k, c))(layer_keys)],
        "ln_f": {"scale": jnp.ones((d,), jnp.float32)},
    }
    if not c["tie_word_embeddings"]:
        params["lm_head"] = jax.random.normal(k_head, (d, v), jnp.float32) * 0.02
    return params


def init_params(key, c: Dict[str, Any]) -> Dict[str, Any]:
    """All weights, float32, on the default device, in one jitted call."""
    return jax.jit(functools.partial(_init, c=c))(key)


# --------------------------------------------------------------------------- #
# layers
# --------------------------------------------------------------------------- #


def gqa(p, x, c, dot: Dot):
    b, s, _ = x.shape
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    q = rope(dot("bsd,de->bse", x, p["wq"]).reshape(b, s, h, dh), c["rope_theta"])
    k = rope(dot("bsd,de->bse", x, p["wk"]).reshape(b, s, kv, dh), c["rope_theta"])
    v = dot("bsd,de->bse", x, p["wv"]).reshape(b, s, kv, dh)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    o = causal_attention(q, k, v, dot).reshape(b, s, h * dh)
    return dot("bse,ed->bsd", o, p["wo"])


def mla(p, x, c, dot: Dot):
    """Latent attention with the keys and values expanded from the latent."""
    b, s, _ = x.shape
    h = c["num_attention_heads"]
    nope, rp, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    q = dot("bsr,re->bse", dot("bsd,dr->bsr", x, p["w_dq"]), p["w_uq"])
    q = q.reshape(b, s, h, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], c["rope_theta"])], -1)
    latent = dot("bsd,dr->bsr", x, p["w_dkv"])
    k_rope = rope(dot("bsd,de->bse", x, p["w_kr"]).reshape(b, s, 1, rp),
                  c["rope_theta"])
    k_nope = dot("bsr,re->bse", latent, p["w_uk"]).reshape(b, s, h, nope)
    v = dot("bsr,re->bse", latent, p["w_uv"]).reshape(b, s, h, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, rp))], -1)
    o = causal_attention(q, k, v, dot).reshape(b, s, h * dv)
    return dot("bse,ed->bsd", o, p["wo"])


def mlp(p, x, dot: Dot):
    hidden = jax.nn.silu(dot("bsd,df->bsf", x, p["w_gate"])) * \
        dot("bsd,df->bsf", x, p["w_up"])
    return dot("bsf,fd->bsd", hidden, p["w_down"])


def moe(p, x, c, dot: Dot):
    """Top-k routed experts with a per-group capacity: tokens are taken in
    groups of ``moe_group_size``; within a group an expert keeps at most
    ``capacity`` assignments, filled first by every token's first choice in
    token order, then by the second choices, and so on.  A token's dropped
    assignments contribute nothing.  Returns (output, balance term)."""
    b, s, d = x.shape
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    gs = min(c["moe_group_size"], b * s)
    cap = int(gs * k * c["moe_capacity_factor"] / e)
    cap = max(4, (cap + 3) // 4 * 4)
    xt = x.reshape(b * s, d)
    probs = jax.nn.softmax(dot("td,de->te", xt, p["router"]), axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)          # (T, k, E)
    grouped = onehot.reshape(-1, gs, k, e)
    taken = jnp.zeros((grouped.shape[0], 1, e), jnp.int32)
    keep = []
    for j in range(k):
        m = grouped[:, :, j]                                   # (G, gs, E)
        slot = jnp.cumsum(m, axis=1) - m + taken
        keep.append(jnp.sum(m * (slot < cap), axis=-1))        # (G, gs)
        taken = taken + jnp.sum(m, axis=1, keepdims=True)
    keep = jnp.stack(keep, axis=-1).reshape(b * s, k)
    weight = jnp.sum(onehot * (gate * keep)[..., None], axis=1)  # (T, E)

    hidden = jax.nn.silu(dot("td,edf->tef", xt, p["moe_w_gate"])) * \
        dot("td,edf->tef", xt, p["moe_w_up"])
    out = dot("tef,efd->td", hidden * weight[..., None], p["moe_w_down"])
    top1 = jnp.mean(jax.nn.one_hot(idx[:, 0], e), axis=0)
    balance = e * jnp.sum(top1 * jnp.mean(probs, axis=0))
    return out.reshape(b, s, d), balance


def layer(p, h, c, dot: Dot):
    eps = c["rms_norm_eps"]
    x = rmsnorm(h, p["ln1"]["scale"], eps)
    h = h + (mla(p["attn"], x, c, dot) if is_mla(c) else gqa(p["attn"], x, c, dot))
    x = rmsnorm(h, p["ln2"]["scale"], eps)
    if is_moe(c):
        y, balance = moe(p["moe"], x, c, dot)
    else:
        y, balance = mlp(p["mlp"], x, dot), jnp.zeros((), jnp.float32)
    return h + y, balance


def layer_slice(params, i):
    return jax.tree.map(lambda x: x[i], params["groups"][0])


def loss(params, tokens, labels, c: Dict[str, Any], dot: Dot = exact_dot,
         trained=None):
    """Mean next-token cross entropy plus the router balance term weighted
    by ``router_aux_loss_coef``.  ``trained`` ({"last": parts of the last
    layer, "ln_f": ...}) stands in for those parts and the final norm: the
    fine-tune."""
    n = c["num_hidden_layers"]
    layers = [layer_slice(params, i) for i in range(n)]
    ln_f = params["ln_f"]
    if trained is not None:
        layers[-1], ln_f = dict(layers[-1], **trained["last"]), trained["ln_f"]
    h = params["embed"][tokens]
    balance = jnp.zeros((), jnp.float32)
    run_layer = jax.checkpoint(functools.partial(layer, c=c, dot=dot))
    for p in layers:
        h, bal = run_layer(p, h)
        balance = balance + bal
    h = rmsnorm(h, ln_f["scale"], c["rms_norm_eps"])
    head = params["embed"].T if c["tie_word_embeddings"] else params["lm_head"]
    logits = dot("bsd,dv->bsv", h, head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - picked)
    return ce + c.get("router_aux_loss_coef", 0.0) * balance if is_moe(c) else ce


# --------------------------------------------------------------------------- #
# the tree split of a fine-tune
# --------------------------------------------------------------------------- #


def trained_part(params, trained):
    """What trains: the whole tree (``"all"``), or the named parts of the
    last layer with the final norm (a list such as ``["attn", "ln1"]``)."""
    if trained == "all":
        return params
    last = layer_slice(params, -1)
    return {"last": {k: last[k] for k in trained}, "ln_f": params["ln_f"]}


def split_last(params):
    """→ (rest, last): the model without its last layer, in the program's
    layout (the embedding, head and final norm with it), and the last
    layer's leaves."""
    stacked = params["groups"][0]
    rest = dict(params, groups=[jax.tree.map(lambda x: x[:-1], stacked)])
    return rest, jax.tree.map(lambda x: x[-1], stacked)


def join_last(rest, last):
    """The inverse of ``split_last``: ``last`` stacked after ``rest``'s layers."""
    # Room for the last layer, then written in place: XLA copies the prefix
    # once and updates a slice, as ``x.at[-1].set`` of the whole stack does.
    # A concatenation compiles to a pad and a maximum of both operands and
    # cost the fine-tune step ~2.5 ms in 245 on a v5e.
    stacked = jax.tree.map(lambda r, x: jnp.pad(r, [(0, 1)] + [(0, 0)] * x.ndim).at[-1].set(x),
                           rest["groups"][0], last)
    return dict(rest, groups=[stacked])


# --------------------------------------------------------------------------- #
# the program's view of the configuration
# --------------------------------------------------------------------------- #


def arch_config(c: Dict[str, Any]):
    """The program's ArchConfig for configuration file ``c``: its registered
    architecture with every size the file states."""
    from repro.configs import MLAConfig, MoEConfig, get_arch

    cfg = get_arch(c["arch"])
    kw: Dict[str, Any] = dict(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_head=c.get("head_dim"), d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], norm_eps=c["rms_norm_eps"],
        rope_theta=c["rope_theta"], tie_embeddings=c["tie_word_embeddings"],
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"])
    if is_moe(c):
        kw["moe"] = MoEConfig(
            n_experts=c["num_local_experts"], top_k=c["num_experts_per_tok"],
            capacity_factor=c["moe_capacity_factor"],
            group_size=c["moe_group_size"],
            router_aux_weight=c["router_aux_loss_coef"])
    if is_mla(c):
        kw["mla"] = MLAConfig(
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"])
    return dataclasses.replace(cfg, **kw)


# --------------------------------------------------------------------------- #
# operations a training step needs
# --------------------------------------------------------------------------- #

# Recomputed operations (rematerialisation), capacity padding and
# masked-out attention scores are not counted: the count is what the step
# requires, so a share of the chip's peak built on it cannot pass 100% by
# counting work the program chose to do.
#
# - A matrix product with N weights costs 2 N per token forward and 4 N
#   backward (gradients of its input and of its weights).
# - Causal attention costs, per token and layer, S H (dk + dv) forward:
#   the scores and the weighted values over the S/2 earlier positions a
#   token sees on average.
# - A routed expert layer runs ``num_experts_per_tok`` experts per token.


def attention_params(c: Dict[str, Any]) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    if is_mla(c):
        nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
        qr, kvr, dv = c["q_lora_rank"], c["kv_lora_rank"], c["v_head_dim"]
        return (d * qr + qr * h * (nope + rope) + d * (kvr + rope)
                + kvr * h * (nope + dv) + h * dv * d)
    dh, kv = head_dim(c), c["num_key_value_heads"]
    return d * dh * (h + 2 * kv) + h * dh * d


def mlp_params(c: Dict[str, Any], active: bool = True) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    if not is_moe(c):
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
    if is_mla(c):
        dk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
        dv = c["v_head_dim"]
    else:
        dk = dv = head_dim(c)
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
