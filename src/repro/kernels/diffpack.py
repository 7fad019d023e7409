"""Pallas TPU kernels: dirty-block compaction (gather) and restore (scatter).

After the dirty-map is computed on device (blockhash.py), the dirty blocks
are packed into a contiguous buffer so a *single* dense DMA ships them to
the host — instead of n_dirty strided host reads. The block indices arrive
via scalar prefetch (``PrefetchScalarGridSpec``), the canonical TPU pattern
for data-dependent addressing: the index vector lands in SMEM before the
grid runs, and each grid step's BlockSpec index_map reads it to choose the
HBM tile to bring into VMEM.

Each block row of ``e`` u32 elements moves as one ``(ROWS, e / ROWS)``
tile — an (8, 2048) tile for a 64 KiB block — because the TPU lowering
takes only blocks whose last two dims are multiples of (8, 128); a
``(1, e)`` row is refused. The row-major view ``(n·ROWS, e / ROWS)`` of
``(n, e)`` keeps every block's bytes in one tile, so the result is the
same gather.

``diffunpack`` is the inverse (restore path): scatter packed blocks back
into the base buffer (aliased in-place via input_output_aliases).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8        # sublanes: each block row moves as a (ROWS, e / ROWS) tile
LANES = 128


def _tile_cols(e: int) -> int:
    if e % (ROWS * LANES):
        raise ValueError(
            f"block of {e} elements does not tile as ({ROWS}, k·{LANES}): "
            f"use a block_bytes that is a multiple of {ROWS * LANES * 4}")
    return e // ROWS


def _copy_kernel(idx_ref, lo_ref, src_ref, dst_ref, *, n: int):
    row = idx_ref[pl.program_id(0)] - lo_ref[0]
    held = jnp.logical_and(row >= 0, row < n)

    @pl.when(held)
    def _copy():
        dst_ref[...] = src_ref[...]

    @pl.when(jnp.logical_not(held))
    def _zero():
        dst_ref[...] = jnp.zeros_like(dst_ref)


def diffpack_pallas(blocks: jnp.ndarray, dirty_idx: jnp.ndarray,
                    row_offset=0, interpret: bool = False) -> jnp.ndarray:
    """Gather: (n_blocks, e) × (n_dirty,) int32 → (n_dirty, e).

    ``blocks`` may be one device's slice of a larger block table, starting
    at global block ``row_offset``: indices outside the slice come back as
    zero rows, so summing the slices' results over devices gathers the
    whole table's dirty blocks."""
    n_dirty = dirty_idx.shape[0]
    n, e = blocks.shape
    c = _tile_cols(e)
    lo = jnp.reshape(jnp.asarray(row_offset, jnp.int32), (1,))

    def src_block(i, idx_ref, lo_ref):
        return jnp.clip(idx_ref[i] - lo_ref[0], 0, n - 1), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_dirty,),
        in_specs=[pl.BlockSpec((ROWS, c), src_block)],
        out_specs=pl.BlockSpec((ROWS, c), lambda i, idx_ref, lo_ref: (i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_copy_kernel, n=n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_dirty * ROWS, c), blocks.dtype),
        interpret=interpret,
    )(dirty_idx, lo, blocks.reshape(n * ROWS, c))
    return out.reshape(n_dirty, e)


def _scatter_kernel(idx_ref, packed_ref, base_ref, out_ref):
    # base is aliased to out; each step overwrites one block row
    out_ref[...] = packed_ref[...]


def diffunpack_pallas(base: jnp.ndarray, packed: jnp.ndarray,
                      dirty_idx: jnp.ndarray, interpret: bool = False
                      ) -> jnp.ndarray:
    """Scatter: write packed rows back at dirty_idx. Returns updated base."""
    n_dirty, e = packed.shape
    n = base.shape[0]
    c = _tile_cols(e)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_dirty,),
        in_specs=[
            pl.BlockSpec((ROWS, c), lambda i, idx_ref: (i, 0)),           # packed
            pl.BlockSpec((ROWS, c), lambda i, idx_ref: (idx_ref[i], 0)),  # base
        ],
        out_specs=pl.BlockSpec((ROWS, c), lambda i, idx_ref: (idx_ref[i], 0)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n * ROWS, c), base.dtype),
        input_output_aliases={2: 0},    # alias base → out (in-place)
        interpret=interpret,
    )(dirty_idx, packed.reshape(n_dirty * ROWS, c), base.reshape(n * ROWS, c))
    return out.reshape(n, e)
