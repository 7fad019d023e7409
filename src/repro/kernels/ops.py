"""Public wrappers for the checkpoint kernels.

Dispatch: Pallas kernels on TPU; vectorized jnp oracle (ref.py) on CPU —
so the diff engine runs everywhere, and tests can force the Pallas path in
``interpret=True`` mode to validate the kernels bit-exactly against ref.
On TPU there is no fallback: a block size the kernels cannot tile raises.

On TPU a leaf's block table is split by rows over the devices that hold
the leaf (``shard_map``): a Mosaic kernel cannot be partitioned by the
compiler, and every block hashes or packs independently, so each device
runs the kernel on its own rows.  A single-device leaf is the one-device
case of the same program.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.context import make_mesh
from repro.kernels import blockhash as bh
from repro.kernels import diffpack as dp
from repro.kernels import ref

DEFAULT_BLOCK_BYTES = 65_536      # 64 KiB — FTI dCP-scale block granularity
ROW_AXIS = "blocks"


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def as_u32_blocks(x: jnp.ndarray, block_bytes: int = DEFAULT_BLOCK_BYTES,
                  row_multiple: int = 1) -> Tuple[jnp.ndarray, int]:
    """Bitcast any array to (n_blocks, block_elems) uint32, zero-padded.
    Returns (blocks, n_blocks); the row count is padded up to a multiple
    of ``row_multiple`` so a kernel's tile grid divides evenly."""
    assert block_bytes % 4 == 0
    be = block_bytes // 4
    flat = x.reshape(-1)
    itemsize = jnp.dtype(flat.dtype).itemsize
    if itemsize == 2:
        # bit-PACK pairs into u32 (little-endian, raw-byte-consistent with
        # numpy .tobytes() — required so diff payloads replay into raw
        # byte buffers on restore)
        u16 = jax.lax.bitcast_convert_type(flat, jnp.uint16)
        pad = (-u16.shape[0]) % 2
        u16 = jnp.pad(u16, (0, pad))
        flat = jax.lax.bitcast_convert_type(u16.reshape(-1, 2), jnp.uint32)
    elif itemsize == 4:
        flat = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    elif itemsize == 8:
        flat = jax.lax.bitcast_convert_type(
            flat.reshape(-1, 1), jnp.uint32).reshape(-1)
    elif itemsize == 1:
        u8 = jax.lax.bitcast_convert_type(flat, jnp.uint8) \
            if flat.dtype != jnp.uint8 else flat
        pad = (-u8.shape[0]) % 4
        u8 = jnp.pad(u8, (0, pad))
        flat = jax.lax.bitcast_convert_type(u8.reshape(-1, 4), jnp.uint32)
    else:
        raise TypeError(f"unsupported dtype {x.dtype}")
    n = flat.shape[0]
    n_blocks = max(1, -(-n // be))
    rows = n_blocks + (-n_blocks) % row_multiple
    flat = jnp.pad(flat, (0, rows * be - n))
    return flat.reshape(rows, be), n_blocks


def row_mesh(x) -> jax.sharding.Mesh:
    """1-D mesh over the devices holding ``x`` (the default device for a
    host array): the devices its block table is split across."""
    sharding = getattr(x, "sharding", None)
    if sharding is not None:
        devices = sorted(sharding.device_set, key=lambda d: d.id)
    else:
        devices = jax.devices()[:1]
    return make_mesh((len(devices),), (ROW_AXIS,), devices=devices)


def _row_blocks(x, block_bytes: int, mesh) -> Tuple[jnp.ndarray, int]:
    blocks, n_blocks = as_u32_blocks(x, block_bytes,
                                     row_multiple=bh.BR * mesh.size)
    return jax.lax.with_sharding_constraint(
        blocks, NamedSharding(mesh, P(ROW_AXIS, None))), n_blocks


# --------------------------------------------------------------------------- #
# blockhash
# --------------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnames=("block_bytes", "mesh",
                                             "interpret"))
def blockhash_pallas(x: jnp.ndarray, block_bytes: int, mesh,
                     interpret: bool = False) -> jnp.ndarray:
    """TPU path: rows of the block table hashed where they live."""
    blocks, n_blocks = _row_blocks(x, block_bytes, mesh)
    h = jax.shard_map(functools.partial(bh.blockhash2_pallas,
                                        interpret=interpret), mesh=mesh,
                      in_specs=P(ROW_AXIS, None), out_specs=P(ROW_AXIS, None),
                      check_vma=False)(blocks)
    return h[:n_blocks]


@functools.partial(jax.jit, static_argnames=("block_bytes",))
def _blockhash_ref(x: jnp.ndarray, block_bytes: int) -> jnp.ndarray:
    blocks, n_blocks = as_u32_blocks(x, block_bytes)
    return ref.blockhash2_ref(blocks)[:n_blocks]


def blockhash(x: jnp.ndarray, block_bytes: int = DEFAULT_BLOCK_BYTES
              ) -> jnp.ndarray:
    """Array → (n_blocks, 2) uint32 digest (64-bit per block)."""
    if _use_pallas():
        return blockhash_pallas(x, block_bytes, row_mesh(x))
    return _blockhash_ref(x, block_bytes)


# --------------------------------------------------------------------------- #
# pack_dirty
# --------------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnames=("n_dirty", "block_bytes",
                                             "mesh", "interpret"))
def pack_dirty_pallas(x: jnp.ndarray, dirty_idx: jnp.ndarray, n_dirty: int,
                      block_bytes: int, mesh, interpret: bool = False
                      ) -> jnp.ndarray:
    """TPU path: each device packs the dirty blocks it holds (zeros for
    the rest) and the partial packs sum across devices."""
    blocks, _ = _row_blocks(x, block_bytes, mesh)

    def local(rows, idx):
        lo = jax.lax.axis_index(ROW_AXIS) * rows.shape[0]
        return jax.lax.psum(dp.diffpack_pallas(rows, idx, row_offset=lo,
                                               interpret=interpret),
                            ROW_AXIS)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(ROW_AXIS, None), P()), out_specs=P(),
                         check_vma=False)(blocks, dirty_idx[:n_dirty])


@functools.partial(jax.jit, static_argnames=("n_dirty", "block_bytes"))
def _pack_dirty_ref(x: jnp.ndarray, dirty_idx: jnp.ndarray, n_dirty: int,
                    block_bytes: int) -> jnp.ndarray:
    blocks, _ = as_u32_blocks(x, block_bytes)
    return ref.diffpack_ref(blocks, dirty_idx[:n_dirty])


def pack_dirty(x: jnp.ndarray, dirty_idx: jnp.ndarray, n_dirty: int,
               block_bytes: int = DEFAULT_BLOCK_BYTES) -> jnp.ndarray:
    """Gather ``n_dirty`` blocks (static count — pad idx with 0s and slice
    host-side) → (n_dirty, block_elems) uint32."""
    if _use_pallas():
        return pack_dirty_pallas(x, dirty_idx, n_dirty, block_bytes,
                                 row_mesh(x))
    return _pack_dirty_ref(x, dirty_idx, n_dirty, block_bytes)


def dirty_indices(h_new: np.ndarray, h_old: Optional[np.ndarray]) -> np.ndarray:
    """Host-side dirty map: blocks whose 64-bit digest changed."""
    if h_old is None:
        return np.arange(h_new.shape[0], dtype=np.int32)
    neq = np.any(np.asarray(h_new) != np.asarray(h_old), axis=1)
    return np.nonzero(neq)[0].astype(np.int32)


def digest_fingerprint(digests) -> str:
    """Collapse a per-block digest table (the device blockhash output)
    into one short hex key.  blake2b over the raw digest bytes: the table
    is tiny (16 B per 64 KiB block), so this costs microseconds while
    standing in for a content hash of the whole leaf — the identity the
    fused upload path uses to reuse chunk layouts without host hashing."""
    import hashlib
    raw = np.ascontiguousarray(np.asarray(digests)).tobytes()
    return hashlib.blake2b(raw, digest_size=16).hexdigest()
