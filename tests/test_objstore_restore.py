"""The objstore acceptance path on a forced-16-device mesh: a sharded
(4×4) level-4 store — one leaf int8-compressed at the chunk level — then
every checkpoint directory (node-local L1–L3 *and* the L4 global dir) is
wiped, and a fresh process restores bit-exact onto a 2×8 mesh from the
object store alone: catalog discovery → chunked file reassembly into the
node-local cache → ``ElasticLoader``/``ShardedLeafRef`` region reads.
``chkls --json`` asserts the remote catalog inventory along the way."""

import subprocess
import sys
import textwrap

SUBPROC_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys
    sys.path.insert(0, "src")
    from repro.dist.context import make_mesh
    import glob, shutil
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.context import CheckpointConfig, CheckpointContext, Protect
    from repro.core.resharding import reshard_tree

    def orig_arrays():
        rng = np.random.default_rng(0)
        return (np.arange(64 * 64, dtype=np.float32).reshape(64, 64),
                rng.normal(size=(64, 32)).astype(np.float32))

    def make_state(mesh):
        w, c = orig_arrays()
        state = {"params": {"w": jnp.asarray(w), "c": jnp.asarray(c)},
                 "step": jnp.int32(7)}
        sh = {"params": {"w": NamedSharding(mesh, P("data", "model")),
                         "c": NamedSharding(mesh, P("data", "model"))},
              "step": NamedSharding(mesh, P())}
        return reshard_tree(state, sh)

    def make_ctx(ckpt_dir):
        ctx = CheckpointContext(CheckpointConfig(
            dir=ckpt_dir, backend="fti", dedicated_thread=False,
            objstore_chunk_bytes=4096))
        ctx.protect(Protect("params/c", compress="int8"), Protect("**"))
        return ctx

    def expected_dequant_c(mesh_shape=(4, 4)):
        # bit-exact expectation: the store quantized each owned shard
        # chunk independently (per-chunk scales)
        from repro.dist.compression import quantize_int8_np, dequantize_int8_np
        _w, c = orig_arrays()
        out = np.empty_like(c)
        rr, cc = c.shape[0] // mesh_shape[0], c.shape[1] // mesh_shape[1]
        for i in range(mesh_shape[0]):
            for j in range(mesh_shape[1]):
                blk = np.ascontiguousarray(
                    c[i*rr:(i+1)*rr, j*cc:(j+1)*cc])
                q, s = quantize_int8_np(blk)
                out[i*rr:(i+1)*rr, j*cc:(j+1)*cc] = \\
                    dequantize_int8_np(q, s, blk.shape)
        return out
""")

STORE_WIPE_SCRIPT = SUBPROC_COMMON + textwrap.dedent("""
    ckpt_dir = sys.argv[1]
    mesh = make_mesh((4, 4), ("data", "model"))
    state = make_state(mesh)
    ctx = make_ctx(ckpt_dir)
    ctx.store(state, id=1, level=4)
    ctx.shutdown()

    # the catalog already covers the multi-file shard set
    from repro.objstore.catalog import Catalog
    from repro.objstore.client import make_object_store
    cat = Catalog(make_object_store(
        "file:" + os.path.join(ckpt_dir, "objstore")))
    entry = cat.entry(1)
    assert entry is not None
    names = sorted(entry["files"])
    assert "rank0.chk5" in names, names
    assert [n for n in names if ".shard" in n], names

    # wipe L1-L3 (node-local, incl. the objstore cache) AND the L4
    # global directory: only the bucket survives
    shutil.rmtree(os.path.join(ckpt_dir, "node-local"))
    for d in glob.glob(os.path.join(ckpt_dir, "global", "ckpt-*")):
        shutil.rmtree(d)
    os.remove(os.path.join(ckpt_dir, "global", "latest"))
    leftovers = [p for p in glob.glob(os.path.join(ckpt_dir, "*"))
                 if os.path.basename(p) != "objstore"]
    assert all(os.path.basename(p) == "global" for p in leftovers), leftovers
    print("STORE-WIPE-OK")
""")

RESTORE_SCRIPT = SUBPROC_COMMON + textwrap.dedent("""
    import io, json, contextlib
    from repro.core.protect import flatten_named
    from repro.tools.chkls import main as chkls_main

    ckpt_dir = sys.argv[1]

    # chkls --json lists the remote catalog (CI-assertable inventory)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert chkls_main([os.path.join(ckpt_dir, "objstore"),
                           "--json"]) == 0
    inv = json.loads(buf.getvalue())["catalog"]
    assert [e["id"] for e in inv["entries"]] == [1]
    e = inv["entries"][0]
    assert e["kind"] == "FULL" and e["level"] == 4
    assert [n for n in e["files"] if ".shard" in n], e["files"]
    assert inv["stored_chunks"] >= e["n_chunks"] > 0

    # the CLI output IS the typed inspect API's inventory: chkls --json
    # must agree field-for-field with a CatalogView over the same bucket
    from repro.objstore.inspect import CatalogView
    root = os.path.join(ckpt_dir, "objstore")
    view = CatalogView.from_root(root, count_chunks=True)
    assert view.to_inventory(root) == inv, "chkls --json drifted from inspect"
    ti = view.entry(1)
    assert ti is not None and ti.kind == e["kind"] and ti.level == e["level"]
    assert ti.n_chunks == e["n_chunks"] and ti.total_bytes == e["total_bytes"]
    assert sorted(f.name for f in ti.files) == sorted(e["files"])
    assert view.latest(kind="FULL").id == 1
    assert len(ti.chunk_digests) <= view.stored_chunks

    # the recovery really is the catalog rung (nothing else exists)
    probe = make_ctx(ckpt_dir)
    got = probe.tcl.backend.engine.load_latest(lazy_sharded=True)
    assert got is not None and got[1]["recovered_via"] == "objstore", got
    probe.shutdown()

    # restore onto a DIFFERENT mesh (2x8) through ElasticLoader regions
    mesh_b = make_mesh((2, 8), ("data", "model"))
    template = jax.tree.map(jnp.zeros_like, make_state(mesh_b))
    ctx = make_ctx(ckpt_dir)
    restored = ctx.load(template)
    assert ctx.restarted
    ctx.shutdown()
    named = flatten_named(restored)[0]
    w, c = orig_arrays()
    assert int(named["step"]) == 7
    np.testing.assert_array_equal(np.asarray(named["params/w"]), w)
    # the compressed leaf restores bit-exact to its per-chunk dequantized
    # values (and within the int8 error envelope of the original)
    got_c = np.asarray(named["params/c"])
    np.testing.assert_array_equal(got_c, expected_dequant_c())
    assert np.abs(got_c - c).max() <= np.abs(c).max() / 127 + 1e-6
    # the cached container records the codec on the shard index
    from repro.core.formats import CHK5Reader
    cache = os.path.join(ckpt_dir, "node-local", "objstore-cache",
                         "ckpt-1", "rank0.chk5")
    rd = CHK5Reader(cache)
    assert rd.info("shardidx/params/c")["attrs"].get("codec") == "int8"
    assert "codec" not in rd.info("shardidx/params/w")["attrs"]
    rd.close()
    print("OBJSTORE-ELASTIC-RESTORE-OK")
""")


def test_objstore_sharded_store_wipe_elastic_restore(tmp_path):
    """Forced-16-device lane: 4×4 sharded L4 store (int8 chunk codec on
    one leaf) → wipe every directory → fresh process restores bit-exact
    on 2×8 from the object store alone."""
    d = str(tmp_path / "ck")
    r = subprocess.run([sys.executable, "-c", STORE_WIPE_SCRIPT, d],
                       capture_output=True, text=True, timeout=540, cwd=".")
    assert "STORE-WIPE-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
    r = subprocess.run([sys.executable, "-c", RESTORE_SCRIPT, d],
                       capture_output=True, text=True, timeout=540, cwd=".")
    assert "OBJSTORE-ELASTIC-RESTORE-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
