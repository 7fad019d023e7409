"""What every plain float32 reference of the benchmark shares.

Straightforward ``jax.numpy``: no kernels, no cache, no program code.  A
configuration names its reference family (``"reference": "<family>"``);
``references/<family>.py`` holds that architecture's weights, layers,
loss, tree split, program mapping and operation count, built from the
pieces here:

- the matrix products: every product goes through ``dot``,
  ``exact_dot`` (float32 at the highest precision) for the reference,
  ``fp8_dot`` for the control, which rounds both operands and the product
  to float8_e4m3 under a per-tensor scale, in the backward pass as in the
  forward;
- ``_dense``, a seeded weight matrix;
- ``rmsnorm``, ``rope`` and ``causal_attention``;
- ``adamw``: AdamW with global-norm clipping and warmup/cosine decay.
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
# weights
# --------------------------------------------------------------------------- #


def _dense(key, d_in: int, shape) -> jnp.ndarray:
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(d_in)


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
