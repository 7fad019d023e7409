"""Differential checkpointing: dirty detection, replay, break-even promote."""
import numpy as np
import jax.numpy as jnp
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
