"""Pallas TPU kernel: per-block checkpoint hashing at HBM bandwidth.

The paper's differential checkpointing (FTI dCP) hashes protected data in
blocks on the host CPU. On TPU that would mean DMA-ing *all* bytes to the
host first — defeating the point. This kernel computes the dirty-map on
device: protected arrays are viewed as (n_blocks, block_elems) uint32 and
hashed in VMEM tiles; only the (tiny) hash vector and the dirty blocks ever
cross the PCIe boundary (DESIGN.md §2, hardware adaptation).

Tiling: grid (n_blocks / BR, block_elems / be); the elems axis is
"arbitrary" (sequential) and accumulates into the output block with a
wrapping-add fold, which matches the commutative oracle in ref.py exactly.
The output is lane-dense: each block row keeps 128 partial sums (one per
lane), folded to one u32 outside the kernel — the TPU lowering refuses a
rank-1 ``(BR,)`` output block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import HASH_SALT_A, HASH_SALT_B

BR = 8          # block rows per tile
BE = 2048       # elems per tile (8·2048·4B = 64 KiB VMEM per input tile)
LANES = 128     # TPU vector lane width: the tiling unit of the last dim


def tile_elems(e: int) -> int:
    """Widest tile ≤ BE that divides a block of ``e`` u32 elements and is
    lane-aligned; raises for a block the TPU tiling cannot cover."""
    be = math.gcd(e, BE)
    if be % LANES:
        raise ValueError(
            f"block of {e} u32 elements is not a multiple of {LANES}: the "
            "TPU blockhash kernel cannot tile it (use a block_bytes that is "
            f"a multiple of {LANES * 4})")
    return be


def _hash_kernel(x_ref, out_ref, *, salt: np.uint32, be: int):
    j = pl.program_id(1)
    x = x_ref[...]                                         # (BR, be) u32
    base = j.astype(jnp.uint32) * np.uint32(be)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1).astype(jnp.uint32)
    h = x ^ ((base + col) * salt)
    h = h ^ (h >> 16)
    h = h * np.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * np.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    # fold the tile's lane groups into one (BR, 128) partial (wrapping add)
    partial = h[:, :LANES]
    for k in range(1, be // LANES):
        partial = partial + h[:, k * LANES:(k + 1) * LANES]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


def blockhash_pallas(blocks_u32: jnp.ndarray, salt: np.uint32 = HASH_SALT_A,
                     interpret: bool = False) -> jnp.ndarray:
    """(n_blocks, elems) uint32 → (n_blocks,) uint32. n_blocks % BR == 0
    (ops.py pads) and elems a multiple of 128."""
    n, e = blocks_u32.shape
    if n % BR:
        raise ValueError(f"n_blocks {n} is not a multiple of {BR}")
    be = tile_elems(e)
    lanes = pl.pallas_call(
        functools.partial(_hash_kernel, salt=salt, be=be),
        grid=(n // BR, e // be),
        in_specs=[pl.BlockSpec((BR, be), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((BR, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(blocks_u32)
    return jnp.sum(lanes, axis=1, dtype=jnp.uint32)


def blockhash2_pallas(blocks_u32: jnp.ndarray, interpret: bool = False
                      ) -> jnp.ndarray:
    """Two salt lanes → (n_blocks, 2) uint32 (64-bit digest)."""
    a = blockhash_pallas(blocks_u32, HASH_SALT_A, interpret=interpret)
    b = blockhash_pallas(blocks_u32, HASH_SALT_B, interpret=interpret)
    return jnp.stack([a, b], axis=1)
