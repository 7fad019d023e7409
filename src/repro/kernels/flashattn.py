"""Pallas TPU flash attention (forward) — beyond-paper perf feature.

The dry-run shows most full-attention cells are *memory-bound* on score
traffic (§Roofline): blockwise attention writes/reads the (S×S_k) score
matrix through HBM. This fused kernel keeps scores in VMEM with the
standard online-softmax recurrence (FlashAttention [arXiv:2205.14135],
tiled for the MXU): grid (batch·heads, q_blocks, kv_blocks), the kv axis
sequential ("arbitrary"), carrying running max/denominator/accumulator in
VMEM scratch.

Used on TPU via ``REPRO_ATTN_IMPL=flash`` (models/attention.py); validated
here in interpret mode against the jnp oracle. The analytic roofline's
``attn_impl="flash"`` knob models exactly this kernel's traffic: no score
HBM round-trip, streaming K/V reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, bq: int, bk: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                     # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                     # (bk, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)

    m_prev = m_scr[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,               # (BH, S, dh) — batch·heads flattened
    k: jnp.ndarray,               # (BH, Sk, dh)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, s, dh = q.shape
    sk = k.shape[1]
    bq = min(bq, s)
    bk = min(bk, sk)
    assert s % bq == 0 and sk % bk == 0, (s, sk, bq, bk)
    scale = 1.0 / np.sqrt(dh)
    grid = (bh, s // bq, sk // bk)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denominator
            pltpu.VMEM((bq, dh), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def flash_attention_bshd(q, k, v, *, causal=True, interpret=False,
                         bq=DEFAULT_BQ, bk=DEFAULT_BK):
    """(B, S, H, dh) convenience wrapper (KV already repeated to H heads)."""
    b, s, h, dh = q.shape

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], dh)

    o = flash_attention(flat(q), flat(k), flat(v), causal=causal,
                        interpret=interpret, bq=bq, bk=bk)
    return o.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
