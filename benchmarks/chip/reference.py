"""Plain float32 reference of the decoder trained in the benchmark's cells.

Straightforward ``jax.numpy``: no kernels, no cache, no program code.  It
follows the model *as the configuration file states it is run* (the keys
of ``configs/<name>.json``), including the places where that differs from
the published model (the file's ``departures`` say which).

- ``init_params`` makes the weights from a key in one jitted call, in the
  tree layout the program consumes (layers stacked on axis 0 under
  ``groups[0]``), so the program and the reference start from the same
  numbers and neither takes anything from the other.
- ``loss`` is next-token cross entropy plus the router's balance term.
- ``adamw`` is AdamW with global-norm clipping and warmup/cosine decay.

Every matrix product goes through ``dot``: ``exact_dot`` (float32 at the
highest precision) for the reference, ``fp8_dot`` for the control, which
rounds both operands and the product to float8_e4m3 under a per-tensor
scale, in the backward pass as in the forward.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

Dot = Callable[[str, jnp.ndarray, jnp.ndarray], jnp.ndarray]
NEG_INF = -1e30
Q_BLOCK = 512


# --------------------------------------------------------------------------- #
# matrix products
# --------------------------------------------------------------------------- #


def exact_dot(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _fp8(x: jnp.ndarray) -> jnp.ndarray:
    """Round to float8_e4m3 under a per-tensor scale (float32 out)."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    y = jnp.clip(x / scale, -448.0, 448.0)      # e4m3fn has no infinity
    return y.astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_dot(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Operands and product in float8_e4m3, as the program keeps both in
    bfloat16; the backward's products likewise (the incoming gradient,
    the saved operands and each product rounded)."""
    return _fp8(exact_dot(spec, _fp8(a), _fp8(b)))


def _fp8_dot_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    # empty arrays carry the operands' dtypes, which the gradients take
    return _fp8(exact_dot(spec, qa, qb)), (qa, qb, a[:0], b[:0])


def _fp8_dot_bwd(spec, res, g):
    qa, qb, ta, tb = res
    _, vjp = jax.vjp(functools.partial(exact_dot, spec), qa, qb)
    da, db = vjp(_fp8(g))
    return _fp8(da).astype(ta.dtype), _fp8(db).astype(tb.dtype)


fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


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


def _dense(key, d_in: int, shape) -> jnp.ndarray:
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(d_in)


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


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary embedding on the halves of the last dim; x (B, S, H, D)."""
    dim, s = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, dot: Dot):
    """q, k (B, S, H, Dk), v (B, S, H, Dv): softmax(q k^T / sqrt(Dk)) v with
    a causal mask, one block of queries at a time."""
    b, s, h, dk = q.shape
    qb = min(Q_BLOCK, s)
    scale = 1.0 / math.sqrt(dk)
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        sc = dot("bqhd,bshd->bhqs", qi, k) * scale
        mask = key_pos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(mask, sc, NEG_INF), axis=-1)
        return dot("bhqs,bshd->bqhd", p, v)

    out = jax.lax.map(block, jnp.arange(s // qb))          # (n, B, qb, H, Dv)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


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
# optimizer
# --------------------------------------------------------------------------- #


def learning_rate(o: Dict[str, Any], count):
    count = jnp.asarray(count, jnp.float32)
    warm = count / max(1.0, o["warmup_steps"])
    prog = jnp.clip((count - o["warmup_steps"]) /
                    max(1.0, o["total_steps"] - o["warmup_steps"]), 0.0, 1.0)
    cos = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return o["lr"] * jnp.where(count < o["warmup_steps"], warm, cos)


def adamw(o: Dict[str, Any], grads, mu, nu, params, count: int):
    """One AdamW step (count is the 1-based step number) on float32 leaves;
    weight decay applies to leaves of two or more dims.  Returns
    (params, mu, nu, clipped grads)."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, o["grad_clip"] / (norm + 1e-9)),
                         grads)
    lr = learning_rate(o, count)
    mu = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, nu, grads)
    b1c, b2c = 1 - o["b1"] ** count, 1 - o["b2"] ** count

    def update(p, m, v):
        step = (m / b1c) / (jnp.sqrt(v / b2c) + o["eps"])
        if p.ndim >= 2:
            step = step + o["weight_decay"] * p
        return p - lr * step

    return jax.tree.map(update, params, mu, nu), mu, nu, grads
