"""Differential checkpointing engine (paper §4.2.3, FTI dCP semantics).

Per protected leaf, a 64-bit digest per ``block_bytes`` block is kept from
the previous checkpoint. On a CHK_DIFF store the new digests are computed
*on device* (Pallas blockhash on TPU; jnp oracle on CPU) for every changed
leaf and cross to the host in one transfer, the dirty map is diffed on host
(tiny), dirty blocks are compacted on device by the diffpack kernel and only
those cross to the host.  Plan waits on the device once, for the digests:
each packed buffer's copy is started in Plan and completes when Pack first
reads ``LeafDelta.payload`` — on the CP thread for asynchronous stores.

Digest cache across stores: jax arrays are immutable, so a leaf that is the
*same object* as at the previous store cannot have changed — its digests
are reused and the blockhash kernel is skipped entirely.  Back-to-back
differential (or full) checkpoints therefore pay hashing only for leaves
that were actually replaced.  Identity is tracked with weakrefs (no device
memory is pinned); mutable ``np.ndarray`` leaves are never skipped.

All digest-state mutation happens in the pipeline's Plan stage, on the
calling thread in submission order — which is what lets DIFF stores run on
a CP-dedicated thread without racing the digest chain.

Break-even guard: the paper measures differential checkpointing to pay off
below a ~95 % dirty ratio (Fig. 7). When the observed ratio exceeds
``promote_threshold`` the engine *promotes* the store to a FULL checkpoint
(cheaper, and it shortens the restore chain).

Restore: FULL base + ordered DIFF deltas are replayed into flat uint32
buffers, then bit-cast back to the leaf dtype/shape.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.formats import dtype_to_str as dtype_str
from repro.core.formats import str_to_dtype as str_dtype
from repro.core.protect import leaf_bytes
from repro.kernels import ops
from repro.telemetry import trace as ttrace


@dataclass
class LeafDelta:
    path: str
    dtype: str
    shape: List[int]
    n_blocks: int
    dirty_idx: np.ndarray        # (n_dirty,) int32
    digests: np.ndarray          # (n_blocks, 2) uint32 — post-store state
    #: (n_pad, block_elems) uint32 packed blocks, padding rows included:
    #: on the device, its host copy in flight, until the first payload read
    blocks: Any = field(repr=False)

    @property
    def in_flight_bytes(self) -> int:
        """Bytes still crossing to the host, the ``n_pad`` padding included."""
        return 0 if isinstance(self.blocks, np.ndarray) else self.blocks.nbytes

    @property
    def payload(self) -> np.ndarray:
        """(n_dirty, block_elems) uint32.  The first read waits for the copy
        and drops the device buffer."""
        if not isinstance(self.blocks, np.ndarray):
            self.blocks = np.asarray(self.blocks)
        return self.blocks[: self.dirty_idx.shape[0]]


@dataclass
class DiffStats:
    total_blocks: int = 0
    dirty_blocks: int = 0
    bytes_written: int = 0
    skipped_leaves: int = 0      # clean by identity — hash kernel not run
    promoted_full: bool = False

    @property
    def dirty_ratio(self) -> float:
        return self.dirty_blocks / max(1, self.total_blocks)


def _pad_count(n_dirty: int) -> int:
    """The dirty count ``pack_dirty`` is compiled for: the next power of two."""
    n_pad = 1
    while n_pad < n_dirty:
        n_pad *= 2
    return n_pad


def _pack_dirty_blocks(leaf: Any, dirty: np.ndarray,
                       block_bytes: int) -> jax.Array:
    """Compact the dirty blocks on device via the diffpack kernel and start
    the packed buffer's copy to the host without waiting for it.

    ``pack_dirty`` jits on a static dirty count, so the index vector is
    padded to the next power of two (bounded number of compiled variants);
    ``LeafDelta.payload`` slices the padding off host-side.  The index
    vector goes to the jitted call as a host array: the call's own transfer
    keeps it on the dispatch fast path, where a separately placed device
    array is re-placed by the slow one."""
    n_dirty = int(dirty.shape[0])
    n_pad = _pad_count(n_dirty)
    idx = np.zeros(n_pad, np.int32)
    idx[:n_dirty] = dirty
    packed = ops.pack_dirty(leaf, idx, n_pad, block_bytes)
    packed.copy_to_host_async()
    return packed


class DiffEngine:
    def __init__(self, block_bytes: int = ops.DEFAULT_BLOCK_BYTES,
                 promote_threshold: float = 0.95):
        self.block_bytes = block_bytes
        self.promote_threshold = promote_threshold
        self._digests: Dict[str, np.ndarray] = {}
        self._clean_refs: Dict[str, "weakref.ref"] = {}
        self.epoch = 0       # bumped on invalidate(); DIFF plans check it

    # ------------------------------------------------------------------ #

    def reset(self) -> None:
        self._digests.clear()
        self._clean_refs.clear()

    def invalidate(self, paths) -> None:
        """Drop digest state for ``paths`` (a store of them failed after the
        chain advanced).  Conservative and safe: the next DIFF sees no base
        for these leaves, marks every block dirty, and the promote guard
        turns that into a FULL — never a delta against phantom data."""
        paths = list(paths)
        for p in paths:
            self._digests.pop(p, None)
            self._clean_refs.pop(p, None)
        if paths:
            self.epoch += 1

    def _is_clean(self, path: str, leaf: Any) -> bool:
        """Same immutable array object as the previous store → unchanged."""
        ref = self._clean_refs.get(path)
        return (ref is not None and ref() is leaf
                and path in self._digests)

    def _remember(self, path: str, leaf: Any) -> None:
        # only immutable arrays make identity a valid clean signal
        if isinstance(leaf, jax.Array) and not isinstance(leaf, np.ndarray):
            try:
                self._clean_refs[path] = weakref.ref(leaf)
                return
            except TypeError:
                pass
        self._clean_refs.pop(path, None)

    def digest_key(self, path: str) -> Optional[str]:
        """Compact fingerprint of ``path``'s current device-side block
        digests, or None when no digest chain exists for it (diff-unaware
        backends, never-stored leaves).  Used as a chunk-layout reuse key
        on the fused Pack → upload path: equal fingerprints mean the
        leaf's bytes are unchanged since the digests were recorded, so
        the chunk stream can replay its previous CDC cut layout instead
        of re-scanning — DIFF-clean leaves never touch host hashing."""
        d = self._digests.get(path)
        if d is None:
            return None
        return ops.digest_fingerprint(d)

    def update_digests_full(self, named: Dict[str, Any]) -> None:
        """After a FULL store: record digests so the next DIFF has a base."""
        for path, leaf in named.items():
            if self._is_clean(path, leaf):
                continue
            self._digests[path] = np.asarray(
                ops.blockhash(leaf, self.block_bytes))
            self._remember(path, leaf)

    def compute_deltas(self, named: Dict[str, Any],
                       ckpt_id: Optional[int] = None
                       ) -> Tuple[Optional[List[LeafDelta]], DiffStats]:
        """→ (deltas, stats); deltas=None means "promote to FULL".
        ``ckpt_id`` labels the ``diff.hash``/``diff.pack`` spans.

        One blocking device→host transfer: the digest tables of every
        changed leaf, hashed first and fetched together.  The deltas'
        packed blocks are still crossing when this returns (see
        ``LeafDelta.payload``)."""
        stats = DiffStats()
        pending: List[Tuple[str, Any, np.ndarray, np.ndarray]] = []
        clean = {p for p, leaf in named.items() if self._is_clean(p, leaf)}
        hashed = [p for p in named if p not in clean]
        stats.skipped_leaves = len(clean)
        with ttrace.span("diff.hash", ckpt_id=ckpt_id,
                         leaves=len(hashed), skipped=len(clean),
                         bytes=leaf_bytes(named[p] for p in hashed),
                         fetches=int(bool(hashed))):
            tables = dict(zip(hashed, jax.device_get(
                [ops.blockhash(named[p], self.block_bytes) for p in hashed])))
            for path, leaf in named.items():
                if path in clean:
                    h_new = self._digests[path]
                    dirty = np.zeros(0, np.int32)
                else:
                    h_new = tables[path]
                    dirty = ops.dirty_indices(h_new, self._digests.get(path))
                stats.total_blocks += h_new.shape[0]
                stats.dirty_blocks += int(dirty.shape[0])
                pending.append((path, leaf, h_new, dirty))

        if stats.dirty_ratio > self.promote_threshold:
            stats.promoted_full = True
            # the promoted FULL store persists exactly these leaves — commit
            # the already-computed digests so the caller need not re-hash
            for path, leaf, h_new, _dirty in pending:
                self._digests[path] = h_new
                self._remember(path, leaf)
            return None, stats

        deltas = []
        counts = [int(d.shape[0]) for _p, _l, _h, d in pending if d.shape[0]]
        stats.bytes_written = stats.dirty_blocks * self.block_bytes
        with ttrace.span("diff.pack", ckpt_id=ckpt_id, leaves=len(counts),
                         dirty_blocks=stats.dirty_blocks,
                         bytes=stats.bytes_written, deferred=len(counts),
                         n_pad="|".join(str(n) for n in
                                        sorted({_pad_count(c) for c in counts}))):
            for path, leaf, h_new, dirty in pending:
                if dirty.shape[0] == 0:
                    blocks = np.zeros((0, self.block_bytes // 4), np.uint32)
                else:
                    blocks = _pack_dirty_blocks(leaf, dirty, self.block_bytes)
                deltas.append(LeafDelta(
                    path=path,
                    dtype=dtype_str(leaf.dtype),
                    shape=list(leaf.shape),
                    n_blocks=int(h_new.shape[0]),
                    dirty_idx=dirty,
                    digests=h_new,
                    blocks=blocks,
                ))
        for d in deltas:
            self._digests[d.path] = d.digests
        for path, leaf, _h, _d in pending:
            self._remember(path, leaf)
        return deltas, stats


# -------------------------------------------------------------------------- #
# restore-side replay
# -------------------------------------------------------------------------- #


def leaf_to_u32_flat(arr: np.ndarray, block_bytes: int) -> np.ndarray:
    be = block_bytes // 4
    raw = np.ascontiguousarray(arr).tobytes()
    pad = (-len(raw)) % 4
    buf = np.frombuffer(raw + b"\x00" * pad, np.uint32)
    n_blocks = max(1, -(-buf.shape[0] // be))
    out = np.zeros(n_blocks * be, np.uint32)
    out[: buf.shape[0]] = buf
    return out


def u32_flat_to_leaf(buf: np.ndarray, dtype: str, shape: List[int]) -> np.ndarray:
    dt = str_dtype(dtype)
    n_bytes = int(np.prod(shape)) * dt.itemsize
    return np.frombuffer(buf.tobytes()[:n_bytes], dtype=dt).reshape(shape).copy()


def apply_delta(buf: np.ndarray, dirty_idx: np.ndarray, payload: np.ndarray,
                block_bytes: int) -> np.ndarray:
    be = block_bytes // 4
    blocks = buf.reshape(-1, be)
    if dirty_idx.shape[0]:
        blocks[dirty_idx] = payload
    return blocks.reshape(-1)
