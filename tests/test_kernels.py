"""Pallas kernels vs jnp oracles — shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.blockhash import (BE, BR, blockhash2_pallas,
                                     blockhash_pallas, tile_elems)
from repro.kernels.diffpack import ROWS, diffpack_pallas, diffunpack_pallas


@pytest.mark.parametrize("rows_mult,elems_mult", [(1, 1), (2, 1), (1, 3), (4, 2)])
def test_blockhash_matches_ref(rows_mult, elems_mult):
    rng = np.random.RandomState(rows_mult * 10 + elems_mult)
    x = rng.randint(0, 2**32, size=(BR * rows_mult, BE * elems_mult),
                    dtype=np.uint64).astype(np.uint32)
    got = np.asarray(blockhash_pallas(jnp.asarray(x), interpret=True))
    want = np.asarray(ref.blockhash_ref(jnp.asarray(x)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("elems", [128, 384, 4096 + 128])
def test_blockhash_narrow_tiles_match_ref(elems):
    """Blocks whose width BE does not divide hash in narrower lane-aligned
    tiles and still match the oracle."""
    rng = np.random.RandomState(elems)
    x = rng.randint(0, 2**32, size=(2 * BR, elems),
                    dtype=np.uint64).astype(np.uint32)
    got = np.asarray(blockhash_pallas(jnp.asarray(x), interpret=True))
    want = np.asarray(ref.blockhash_ref(jnp.asarray(x)))
    assert np.array_equal(got, want)


def test_blockhash_refuses_untileable_blocks():
    with pytest.raises(ValueError, match="multiple of 128"):
        tile_elems(100)
    with pytest.raises(ValueError, match="multiple of"):
        blockhash_pallas(jnp.zeros((BR - 1, BE), jnp.uint32), interpret=True)


def test_blockhash2_two_lanes_differ():
    rng = np.random.RandomState(0)
    x = rng.randint(0, 2**32, size=(BR, BE), dtype=np.uint64).astype(np.uint32)
    h = np.asarray(blockhash2_pallas(jnp.asarray(x), interpret=True))
    assert h.shape == (BR, 2)
    assert not np.array_equal(h[:, 0], h[:, 1])
    assert np.array_equal(h, np.asarray(ref.blockhash2_ref(jnp.asarray(x))))


@pytest.mark.parametrize("n_blocks,cols,n_dirty",
                         [(8, 128, 3), (16, 256, 16), (4, 512, 1)])
def test_diffpack_matches_ref(n_blocks, cols, n_dirty):
    # a block row moves as one (ROWS, cols) tile: ROWS·cols elements
    rng = np.random.RandomState(n_blocks)
    blocks = rng.randn(n_blocks, ROWS * cols).astype(np.float32)
    idx = rng.choice(n_blocks, size=n_dirty, replace=False).astype(np.int32)
    got = np.asarray(diffpack_pallas(jnp.asarray(blocks), jnp.asarray(idx),
                                     interpret=True))
    want = np.asarray(ref.diffpack_ref(jnp.asarray(blocks), jnp.asarray(idx)))
    assert np.array_equal(got, want)


def test_diffunpack_matches_ref():
    rng = np.random.RandomState(3)
    base = rng.randn(16, ROWS * 128).astype(np.float32)
    idx = np.array([1, 7, 13], np.int32)
    packed = rng.randn(3, ROWS * 128).astype(np.float32)
    got = np.asarray(diffunpack_pallas(
        jnp.asarray(base), jnp.asarray(packed), jnp.asarray(idx),
        interpret=True))
    want = np.asarray(ref.diffunpack_ref(
        jnp.asarray(base), jnp.asarray(packed), jnp.asarray(idx)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32,
                                   jnp.float64, jnp.uint8])
def test_ops_blockhash_dtypes(dtype):
    if dtype == jnp.float64:
        x = jnp.arange(1000).astype(jnp.float32).astype(dtype)
    else:
        x = jnp.arange(1000).astype(dtype)
    h = ops.blockhash(x, 256)
    assert h.dtype == jnp.uint32 and h.shape[1] == 2
    # deterministic
    assert np.array_equal(np.asarray(h), np.asarray(ops.blockhash(x, 256)))
    # sensitive to any element change (use a value exactly representable in
    # every tested dtype — bf16 rounds 999+1 back to 1000 == original)
    x2 = x.at[999].set(jnp.asarray(-5).astype(dtype))
    assert not np.array_equal(np.asarray(h),
                              np.asarray(ops.blockhash(x2, 256)))


def test_ops_blockhash_never_falls_back_on_tpu(monkeypatch):
    """With the Pallas dispatch on (the TPU path), a block size the kernel
    cannot tile raises instead of quietly hashing with the oracle."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    x = jnp.zeros((1000,), jnp.float32)
    with pytest.raises(ValueError, match="cannot tile"):
        ops.blockhash(x, 200)
    with pytest.raises(ValueError, match="does not tile"):
        ops.pack_dirty(x, jnp.zeros((1,), jnp.int32), 1, 512)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.uint8])
def test_ops_tpu_path_matches_ref(dtype):
    """The TPU wrappers (row-split block table, both kernels) in interpret
    mode give the oracle's digests and packed blocks bit for bit."""
    block_bytes = 4 * ROWS * 128
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randint(0, 255, size=(37, 1000)).astype(np.float32)
                    ).astype(dtype)
    mesh = ops.row_mesh(x)
    got = ops.blockhash_pallas(x, block_bytes, mesh, interpret=True)
    want = ops.blockhash(x, block_bytes)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    idx = jnp.asarray([0, 3, 4, 0], jnp.int32)
    got = ops.pack_dirty_pallas(x, idx, 3, block_bytes, mesh, interpret=True)
    want = ops.pack_dirty(x, idx, 3, block_bytes)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_ops_dirty_indices():
    h1 = np.zeros((10, 2), np.uint32)
    h2 = h1.copy()
    h2[3, 0] = 1
    h2[7, 1] = 9
    assert ops.dirty_indices(h2, h1).tolist() == [3, 7]
    assert ops.dirty_indices(h2, None).tolist() == list(range(10))


def test_ops_pack_dirty_roundtrip():
    x = jnp.arange(4096, dtype=jnp.float32)
    idx = jnp.asarray([0, 5], dtype=jnp.int32)
    packed = ops.pack_dirty(x, idx, 2, 256)
    blocks, _ = ops.as_u32_blocks(x, 256)
    assert np.array_equal(np.asarray(packed),
                          np.asarray(blocks)[np.asarray(idx)])
