"""Differential checkpointing: dirty detection, replay, break-even promote."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.diff import (
    DiffEngine,
    apply_delta,
    leaf_to_u32_flat,
    u32_flat_to_leaf,
)
from repro.kernels import ops

BB = 256          # small blocks for tests


def test_first_diff_is_all_dirty():
    eng = DiffEngine(block_bytes=BB)
    a = jnp.arange(1000, dtype=jnp.float32)
    deltas, stats = eng.compute_deltas({"a": a})
    # no base digests → every block dirty → promoted to full
    assert stats.dirty_ratio == 1.0
    assert deltas is None and stats.promoted_full


def test_clean_store_no_dirty():
    eng = DiffEngine(block_bytes=BB)
    a = jnp.arange(1000, dtype=jnp.float32)
    eng.update_digests_full({"a": a})
    deltas, stats = eng.compute_deltas({"a": a})
    assert stats.dirty_blocks == 0
    assert deltas is not None and deltas[0].dirty_idx.size == 0


def test_single_element_change_one_block():
    eng = DiffEngine(block_bytes=BB)
    a = jnp.arange(1000, dtype=jnp.float32)
    eng.update_digests_full({"a": a})
    b = a.at[500].set(-1.0)
    deltas, stats = eng.compute_deltas({"a": b})
    assert stats.dirty_blocks == 1
    assert deltas[0].dirty_idx.tolist() == [500 * 4 // BB]


def test_promote_threshold():
    eng = DiffEngine(block_bytes=BB, promote_threshold=0.5)
    a = jnp.arange(1024, dtype=jnp.float32)
    eng.update_digests_full({"a": a})
    deltas, stats = eng.compute_deltas({"a": a + 1.0})   # everything dirty
    assert deltas is None and stats.promoted_full


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(10, 3000),
       n_edits=st.integers(0, 20),
       dtype=st.sampled_from(["float32", "int32", "float16", "uint8"]))
def test_replay_reconstructs_exactly(seed, n, n_edits, dtype):
    """full base + chain of diffs replays to the exact final array."""
    rng = np.random.RandomState(seed)
    base = np.abs(rng.randn(n) * 10).astype(dtype)
    eng = DiffEngine(block_bytes=BB)
    eng.update_digests_full({"x": jnp.asarray(base)})

    buf = leaf_to_u32_flat(base, BB)
    cur = base.copy()
    for _ in range(3):
        for _ in range(n_edits):
            i = rng.randint(0, n)
            cur[i] = np.asarray(abs(rng.randn()) * 10).astype(dtype)
        deltas, stats = eng.compute_deltas({"x": jnp.asarray(cur)})
        if deltas is None:          # promoted to FULL (past break-even)
            eng.update_digests_full({"x": jnp.asarray(cur)})
            buf = leaf_to_u32_flat(cur, BB)
            continue
        d = deltas[0]
        buf = apply_delta(buf, d.dirty_idx, d.payload, BB)
    got = u32_flat_to_leaf(buf, np.dtype(dtype).str, [n])
    assert np.array_equal(got, cur)


def test_bf16_roundtrip_through_u32():
    import ml_dtypes
    a = np.arange(7).astype(ml_dtypes.bfloat16)
    buf = leaf_to_u32_flat(a, BB)
    got = u32_flat_to_leaf(buf, "bfloat16", [7])
    assert np.array_equal(got.astype(np.float32), a.astype(np.float32))


def test_hash_collision_resistance_smoke():
    """changed bytes change the digest (salted 64-bit lanes)."""
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(4096).astype(np.float32))
    h1 = np.asarray(ops.blockhash(a, BB))
    flips = 0
    for i in rng.randint(0, 4096, size=50):
        b = a.at[int(i)].set(a[int(i)] + 1.0)
        h2 = np.asarray(ops.blockhash(b, BB))
        if not np.array_equal(h1, h2):
            flips += 1
    assert flips == 50


# -------------------------------------------------------------------------- #
# deferred packed copies: Plan starts each pack's host copy, the first
# payload read completes it
# -------------------------------------------------------------------------- #

N_BLOCKS = 6                       # not a power of two: the pack pads to 8
DIRTY_SETS = {0: [], 1: [4], 3: [1, 3, 5], "all": list(range(N_BLOCKS))}


def _leaf_and_edit(dtype, dirty_blocks):
    """A host leaf of ``N_BLOCKS`` blocks (the last one partial) and a copy
    whose low byte is flipped at the start of each block in
    ``dirty_blocks`` (mantissa bits only: no NaN is made)."""
    import ml_dtypes
    dt = np.dtype(ml_dtypes.bfloat16 if dtype == "bfloat16" else dtype)
    n = (N_BLOCKS * BB - 40) // dt.itemsize
    rng = np.random.RandomState(7)
    base = (rng.rand(n) * 100).astype(dt)
    cur = base.copy()
    raw = cur.view(np.uint8)
    for b in dirty_blocks:
        raw[b * BB] ^= 1
    return base, cur


def _payload_packed_synchronously(leaf, dirty):
    """The packed payload as a blocking copy of the padded pack gives it."""
    n_pad = 1
    while n_pad < dirty.shape[0]:
        n_pad *= 2
    idx = np.zeros(n_pad, np.int32)
    idx[: dirty.shape[0]] = dirty
    return np.asarray(ops.pack_dirty(leaf, jnp.asarray(idx), n_pad, BB)
                      )[: dirty.shape[0]]


@pytest.mark.parametrize("n_dirty", list(DIRTY_SETS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "uint8"])
def test_deferred_payload_matches_blocking_pack(dtype, n_dirty):
    """The payload read after Plan is, byte for byte, the blocking pack's
    (the ``n_pad`` slice included); ``bytes_written`` counts the dirty
    blocks; replay reconstructs the leaf bit-exactly."""
    dirty_blocks = DIRTY_SETS[n_dirty]
    base, cur = _leaf_and_edit(dtype, dirty_blocks)
    eng = DiffEngine(block_bytes=BB, promote_threshold=1.0)
    eng.update_digests_full({"a": jnp.asarray(base)})
    x = jnp.asarray(cur)
    deltas, stats = eng.compute_deltas({"a": x})
    (d,) = deltas
    assert d.dirty_idx.tolist() == dirty_blocks
    assert stats.bytes_written == len(dirty_blocks) * BB
    n_pad = {0: 0, 1: 1, 3: 4, N_BLOCKS: 8}[len(dirty_blocks)]
    assert d.in_flight_bytes == n_pad * BB      # still on the device
    expect = _payload_packed_synchronously(x, d.dirty_idx)
    got = d.payload
    assert d.in_flight_bytes == 0 and isinstance(d.blocks, np.ndarray)
    assert got.dtype == np.uint32 and got.shape == (len(dirty_blocks), BB // 4)
    assert got.tobytes() == expect.tobytes()
    buf = apply_delta(leaf_to_u32_flat(base, BB), d.dirty_idx, got, BB)
    replayed = u32_flat_to_leaf(buf, d.dtype, d.shape)
    assert replayed.tobytes() == cur.tobytes()
    # the digest chain stays a host table per path
    assert isinstance(eng._digests["a"], np.ndarray)
    assert eng._digests["a"].shape == (N_BLOCKS, 2)


def test_deferred_payload_outlives_the_source_leaf():
    """Plan holds the packed buffer and nothing more: the payload still
    reads right after the leaf it was packed from is deleted."""
    base, cur = _leaf_and_edit("float32", [1, 3, 5])
    eng = DiffEngine(block_bytes=BB)
    eng.update_digests_full({"a": jnp.asarray(base)})
    x = jnp.asarray(cur)
    deltas, _stats = eng.compute_deltas({"a": x})
    expect = _payload_packed_synchronously(x, deltas[0].dirty_idx)
    x.delete()
    assert deltas[0].payload.tobytes() == expect.tobytes()
