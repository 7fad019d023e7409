"""The checkpoint kernels compiled by the TPU compiler, without a chip.

Every TPU compile test lives in this one file: the topology is described
in a module-scoped fixture, so only the worker that runs this file loads
the TPU compiler library, and every worker collects the same tests.

The kernels compile for a described ``v5e:2x2`` chip at the leaf sizes of
tinyllama-1.1b's checkpoint: the 32000×2048 f32 embedding (4000 blocks of
64 KiB), one 2048×5632 f32 MLP weight and the same weight in bf16.  They
go through the ``ops`` TPU wrappers (the dispatch would pick the jnp
oracle on this CPU backend), so what compiles here is the device path a
DIFF store runs on the chip, on one chip and sharded over four.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.dist.context import make_mesh
from repro.kernels import ops
from repro.kernels.diffpack import diffunpack_pallas
from repro.kernels.flashattn import flash_attention

LEAVES = {
    "embed_f32": ((32_000, 2_048), jnp.float32),
    "mlp_f32": ((2_048, 5_632), jnp.float32),
    "mlp_bf16": ((2_048, 5_632), jnp.bfloat16),
}
N_DIRTY = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described chip's compile can be written to the persistent cache
    # but never read back: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler library to describe one
            jax.config.update("jax_enable_compilation_cache", prev)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return make_mesh((2, 2), ("data", "model"), devices=topo.devices)


def _compile(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


def _blockhash(x):
    return ops.blockhash_pallas.lower(x, ops.DEFAULT_BLOCK_BYTES,
                                      ops.row_mesh(x))


def _pack(x, idx):
    return ops.pack_dirty_pallas.lower(x, idx, N_DIRTY,
                                       ops.DEFAULT_BLOCK_BYTES,
                                       ops.row_mesh(x))


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_blockhash_compiles_for_v5e(leaf, one_chip):
    shape, dtype = LEAVES[leaf]
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lowered = _blockhash(x)
    n_blocks = -(-x.size * jnp.dtype(dtype).itemsize
                 // ops.DEFAULT_BLOCK_BYTES)
    assert lowered.out_info.shape == (n_blocks, 2)
    _compile(lowered)


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_diffpack_compiles_for_v5e(leaf, one_chip):
    shape, dtype = LEAVES[leaf]
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((N_DIRTY,), jnp.int32, sharding=one_chip)
    _compile(_pack(x, idx))


@pytest.mark.parametrize("spec", [P("model", None), P("data", "model")])
def test_kernels_compile_for_sharded_leaf_on_2x2(spec, mesh_2x2):
    """A leaf sharded over the four chips: the compiler cannot partition a
    Mosaic kernel, so the ops wrappers split the block table by rows."""
    shape, dtype = LEAVES["embed_f32"]
    x = jax.ShapeDtypeStruct(shape, dtype,
                             sharding=NamedSharding(mesh_2x2, spec))
    idx = jax.ShapeDtypeStruct((N_DIRTY,), jnp.int32,
                               sharding=NamedSharding(mesh_2x2, P()))
    _compile(_blockhash(x))
    _compile(_pack(x, idx))


def test_diffunpack_compiles_for_v5e(one_chip):
    e = ops.DEFAULT_BLOCK_BYTES // 4
    base = jax.ShapeDtypeStruct((4_000, e), jnp.uint32, sharding=one_chip)
    packed = jax.ShapeDtypeStruct((N_DIRTY, e), jnp.uint32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((N_DIRTY,), jnp.int32, sharding=one_chip)
    _compile(jax.jit(diffunpack_pallas).lower(base, packed, idx))


def test_flash_attention_compiles_for_v5e(one_chip):
    # tinyllama heads: batch 4 × 32 heads, head_dim 64, 512 tokens
    q = jax.ShapeDtypeStruct((4 * 32, 512, 64), jnp.bfloat16,
                             sharding=one_chip)
    _compile(jax.jit(flash_attention).lower(q, q, q))
