"""The block digest a save records for a leaf, computed again in NumPy.

The definition, written here from the checkpoint format and not taken
from the program: the leaf's bytes as little-endian 32-bit words (16-bit
elements packed in pairs), zero-padded to whole blocks of
``block_bytes``; per block and per salt, the wrapping 32-bit sum over its
words of ``fmix32(word ^ (position * salt))``, where ``fmix32`` is
murmur3's finalizer.  Two salts give a 64-bit digest per block.
"""
from __future__ import annotations

import numpy as np

SALTS = (0x9E3779B9, 0x85EBCA6B)


def _fmix32(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


def words(leaf: np.ndarray, block_bytes: int) -> np.ndarray:
    """(n_blocks, block_bytes // 4) uint32: the leaf's bytes, zero-padded."""
    raw = np.ascontiguousarray(leaf).reshape(-1).view(np.uint8)
    per = block_bytes // 4
    n = max(1, -(-raw.size // block_bytes))
    out = np.zeros(n * block_bytes, np.uint8)
    out[:raw.size] = raw
    return out.view("<u4").reshape(n, per)


def block_digests(leaf: np.ndarray, block_bytes: int, rows: int = 256) -> np.ndarray:
    """(n_blocks, 2) uint32 digests of ``leaf``, ``rows`` blocks at a time."""
    blocks = words(leaf, block_bytes)
    pos = np.arange(blocks.shape[1], dtype=np.uint32)
    out = np.empty((blocks.shape[0], len(SALTS)), np.uint32)
    with np.errstate(over="ignore"):
        salted = [pos * np.uint32(s) for s in SALTS]
        for lo in range(0, blocks.shape[0], rows):
            part = blocks[lo:lo + rows]
            for j, sp in enumerate(salted):
                out[lo:lo + rows, j] = np.sum(_fmix32(part ^ sp), axis=1, dtype=np.uint32)
    return out
