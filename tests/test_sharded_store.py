"""Shard-local stores: the multi-file ``.tmp``-until-commit invariant
(crash after k of n shard files staged → checkpoint not restorable,
recovery falls back), manifest coverage of shard sets, the sharded CHK5
layout + ElasticLoader region reads, and — in a forced-16-device
subprocess — the no-gather Plan guarantee, a store → crash → restore
cycle, and the ``chkls --json`` shard inventory."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro.core.resharding as rs
from repro.core import manifest as mf
from repro.core.comm import LocalComm
from repro.core.formats import CHK5Writer
from repro.core.resharding import (
    ElasticLoader,
    ShardChunk,
    ShardSnapshot,
    write_shard_files,
)
from repro.core.storage import StorageConfig, StorageEngine, StoreRequest


def _engine(tmp_path):
    cfg = StorageConfig(root=str(tmp_path / "shared"), block_bytes=256)
    return StorageEngine(cfg, LocalComm(str(tmp_path / "nl")))


def _sharded_plan(eng, ckpt_id, n_chunks=4, rows=16, cols=8):
    """A Plan carrying a hand-built shard snapshot (host chunks — the
    snapshot machinery accepts np data, so the multi-file commit protocol
    is testable without a multi-device mesh)."""
    plan = eng.pipeline.plan(StoreRequest(
        named={"step": np.int32(ckpt_id)}, ckpt_id=ckpt_id, level=1))
    full = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
    per = rows // n_chunks
    chunks = [ShardChunk(offset=(k * per, 0), shape=(per, cols),
                         data=full[k * per:(k + 1) * per])
              for k in range(n_chunks)]
    plan.sharded = {"w": ShardSnapshot(
        dtype="<f4", global_shape=(rows, cols), chunks=chunks)}
    return plan, full


def test_crash_mid_shard_write_stays_tmp_and_falls_back(tmp_path,
                                                        monkeypatch):
    """Kill the store after k of n shard files are written: the whole set
    stays in ``.tmp``, the checkpoint is not listed as restorable, and
    recovery falls back to the previous id."""
    eng = _engine(tmp_path)
    eng.store({"w": np.ones(64, np.float32)}, ckpt_id=1, level=1)

    real_writer = rs.CHK5Writer
    made = []

    class ExplodingWriter(real_writer):
        def __init__(self, path, **kw):
            if ".shard" in os.path.basename(path):
                made.append(path)
                if len(made) > 2:       # k=2 of n staged, then crash
                    raise RuntimeError("simulated crash mid-shard-write")
            super().__init__(path, **kw)

    monkeypatch.setattr(rs, "CHK5Writer", ExplodingWriter)
    plan, _ = _sharded_plan(eng, 2, n_chunks=8)
    with pytest.raises(RuntimeError, match="mid-shard-write"):
        eng.pipeline.finish(plan)
    monkeypatch.setattr(rs, "CHK5Writer", real_writer)

    root = eng.pipeline.tier_root(1)
    assert os.path.isdir(mf.ckpt_dir(root, 2, tmp=True))   # staged, not
    assert not os.path.isdir(mf.ckpt_dir(root, 2))         # committed
    assert mf.list_committed(root) == [1]
    named, meta = eng.load_latest()
    assert meta["id"] == 1 and named["w"][0] == 1.0


def test_committed_shard_set_with_lost_file_not_restorable(tmp_path):
    """Post-commit loss of one shard file: the manifest detects the
    incomplete set and the restore walk falls back to the previous id
    instead of assembling a partial leaf."""
    eng = _engine(tmp_path)
    eng.store({"w": np.ones(64, np.float32), "step": np.int32(1)},
              ckpt_id=1, level=1)
    plan, full = _sharded_plan(eng, 2)
    eng.pipeline.finish(plan)

    root = eng.pipeline.tier_root(1)
    man = mf.read_manifest(root, 2)
    files = mf.manifest_files(man)
    shard_files = [f for f in files if ".shard" in f]
    assert "rank0.chk5" in files and len(shard_files) == 4
    assert mf.missing_files(root, 2) == []

    # intact: the sharded leaf restores (materialized) bit-exact
    named, meta = eng.load_latest()
    assert meta["id"] == 2
    np.testing.assert_array_equal(named["w"], full)

    os.remove(os.path.join(mf.ckpt_dir(root, 2), shard_files[1]))
    assert mf.missing_files(root, 2) == [shard_files[1]]
    named, meta = eng.load_latest()       # falls back — never partial data
    assert meta["id"] == 1
    assert int(named["step"]) == 1


def test_partner_tier_replicates_shard_set_across_node_loss(tmp_path):
    """L2: the whole multi-file shard set is replicated to the ring
    partner, so a lost node's sharded checkpoint restores from partner
    copies (rank<k>.partner<j>.shard<s>.chk5)."""
    from repro.core.comm import SimulatedCluster
    cluster = SimulatedCluster(str(tmp_path / "cluster"), 4)
    cfg = StorageConfig(root=str(tmp_path / "shared"), group_size=4,
                        block_bytes=256)
    engines = [StorageEngine(cfg, c) for c in cluster.comms]
    fulls = {}
    for r, eng in enumerate(engines):
        plan, full = _sharded_plan(eng, 1)
        plan.level = 2
        plan.tiers = eng.pipeline.tier_stack(2)
        plan.root = plan.tiers[0].root
        eng.pipeline.finish(plan)
        fulls[r] = full

    victim = 1
    cluster.kill_node(victim)
    got = engines[victim].load_latest()
    assert got is not None, "L2 sharded recovery failed after node loss"
    named, meta = got
    assert meta["recovered_via"] == "partner"
    np.testing.assert_array_equal(named["w"], fulls[victim])


def test_shard_layout_roundtrip_and_elastic_regions(tmp_path):
    """write_shard_files → ElasticLoader: multi-dim chunks reassemble any
    region; the legacy axis-0 layout reads through the same loader."""
    d = str(tmp_path)
    full = np.arange(12 * 10, dtype=np.float32).reshape(12, 10)
    chunks = [ShardChunk(offset=(r * 6, c * 5), shape=(6, 5),
                         data=full[r * 6:(r + 1) * 6, c * 5:(c + 1) * 5])
              for r in range(2) for c in range(2)]
    with CHK5Writer(os.path.join(d, "rank0.chk5")) as w:
        files = write_shard_files(
            d, "rank0", w,
            {"w": ShardSnapshot("<f4", (12, 10), chunks)}, max_writers=3)
    assert len(files) == 3 and all(os.path.exists(p) for p in files)

    loader = ElasticLoader(sorted(files))
    assert loader.names() == ["w"]
    assert loader.global_shape("w") == [12, 10]
    np.testing.assert_array_equal(loader.read_region("w", None), full)
    np.testing.assert_array_equal(
        loader.read_region("w", (slice(3, 9), slice(2, 8))),
        full[3:9, 2:8])
    np.testing.assert_array_equal(loader.read_rows("w", 5, 7), full[5:7])
    with pytest.raises(ValueError, match="not fully covered"):
        ElasticLoader(sorted(files)[:1]).read_region("w", None)
    loader.close()

    # legacy axis-0 chunk files read through the same loader
    legacy = os.path.join(d, "legacy.chk5")
    rs.save_sharded(legacy, {"v": full[4:]}, {"v": 4},
                    {"v": [12, 10]})
    lo = ElasticLoader([legacy])
    np.testing.assert_array_equal(lo.read_rows("v", 6, 10), full[6:10])
    lo.close()

    # OVERLAPPING chunk files (replicated shards merged from several rank
    # files) must assemble, not double-count coverage — regression: the
    # volume-sum check rejected fully-covered overlapping sets
    a = os.path.join(d, "ov-a.chk5")
    b = os.path.join(d, "ov-b.chk5")
    rs.save_sharded(a, {"v": full[0:8]}, {"v": 0}, {"v": [12, 10]})
    rs.save_sharded(b, {"v": full[5:12]}, {"v": 5}, {"v": [12, 10]})
    lo = ElasticLoader([a, b])
    np.testing.assert_array_equal(lo.read_region("v", None), full)
    np.testing.assert_array_equal(lo.read_rows("v", 3, 11), full[3:11])
    lo.close()
    # a genuine hole still raises, overlap or not
    c = os.path.join(d, "ov-c.chk5")
    rs.save_sharded(c, {"v": full[9:12]}, {"v": 9}, {"v": [12, 10]})
    lo = ElasticLoader([a, c])
    with pytest.raises(ValueError, match="not fully covered"):
        lo.read_region("v", None)
    lo.close()


def test_shard_chunk_int8_codec_roundtrip_regions_and_verify(tmp_path):
    """``compress="int8"`` reaches shard chunks: each chunk quantizes
    independently (scales in the same shard file), region reads decode
    only the touched blocks, a full-chunk read verifies the recorded
    dequantized crc32, and corruption is caught — closing the ROADMAP
    "chunks ship raw" gap."""
    from repro.core.formats import CHK5CorruptionError, CHK5Reader
    from repro.core.protect import Protect
    from repro.core.resharding import resolve_shard_refs
    from repro.dist.compression import dequantize_int8_np, quantize_int8_np

    d = str(tmp_path)
    rng = np.random.default_rng(1)
    full = rng.normal(size=(16, 10)).astype(np.float32)
    chunks = [ShardChunk(offset=(r * 8, c * 5), shape=(8, 5),
                         data=full[r * 8:(r + 1) * 8, c * 5:(c + 1) * 5])
              for r in range(2) for c in range(2)]
    with CHK5Writer(os.path.join(d, "rank0.chk5")) as w:
        files = write_shard_files(
            d, "rank0", w, {"w": ShardSnapshot("<f4", (16, 10), chunks)},
            specs={"w": Protect("w", compress="int8")}, max_writers=2)

    exp = np.empty_like(full)
    for c in chunks:
        q, s = quantize_int8_np(np.ascontiguousarray(c.data))
        back = dequantize_int8_np(q, s, c.data.shape).astype(np.float32)
        exp[c.offset[0]:c.offset[0] + 8, c.offset[1]:c.offset[1] + 5] = back

    loader = ElasticLoader(sorted(files))
    np.testing.assert_array_equal(loader.read_region("w", None), exp)
    np.testing.assert_array_equal(                   # partial-block decode
        loader.read_region("w", (slice(3, 13), slice(2, 9))),
        exp[3:13, 2:9])
    loader.close()
    assert np.abs(exp - full).max() <= np.abs(full).max() / 127 + 1e-6

    # lazy-ref restore path (what the pipeline hands TCL) decodes too
    rd = CHK5Reader(os.path.join(d, "rank0.chk5"))
    assert rd.info("shardidx/w")["attrs"].get("codec") == "int8"
    refs = resolve_shard_refs(rd, [d], 0)
    np.testing.assert_array_equal(refs["w"].materialize(), exp)
    rd.close()

    # per-chunk attrs: codec + scales dataset + dequantized crc32
    frd = CHK5Reader(sorted(files)[0])
    ds = [x for x in frd.datasets() if x.startswith("shard/")][0]
    attrs = frd.info(ds)["attrs"]
    assert attrs["codec"] == "int8" and "roundtrip_crc32" in attrs
    assert f"codecaux/{ds}/scale" in frd.datasets()
    off = frd.info(ds)["offset"]
    frd.close()

    # flip one payload byte: the full-chunk dequantized-crc verify trips
    with open(sorted(files)[0], "r+b") as fh:
        fh.seek(off)
        b = fh.read(1)
        fh.seek(off)
        fh.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(CHK5CorruptionError, match="roundtrip"):
        ElasticLoader(sorted(files)).read_region("w", None)


def test_shard_chunk_int8_fallbacks(tmp_path):
    """Non-float leaves and chunks whose roundtrip error exceeds
    ``max_error`` ship raw, with the reason recorded per dataset."""
    from repro.core.formats import CHK5Reader
    from repro.core.protect import Protect

    d = str(tmp_path)
    ints = np.arange(40, dtype=np.int32).reshape(8, 5)
    # random normals roundtrip with ~1e-3 relative L2 — any bound tighter
    # than that trips the per-chunk fallback
    wild = np.random.default_rng(2).normal(size=(4, 2)).astype(np.float32)
    snaps = {
        "i": ShardSnapshot("<i4", (8, 5),
                           [ShardChunk((0, 0), (8, 5), ints)]),
        "f": ShardSnapshot("<f4", (4, 2),
                           [ShardChunk((0, 0), (4, 2), wild)]),
    }
    specs = {"i": Protect("i", compress="int8"),
             "f": Protect("f", compress="int8", max_error=1e-9)}
    with CHK5Writer(os.path.join(d, "rank0.chk5")) as w:
        files = write_shard_files(d, "rank0", w, snaps, specs=specs,
                                  max_writers=1)
    rd = CHK5Reader(os.path.join(d, "rank0.chk5"))
    assert "codec_fallback" in rd.info("shardidx/i")["attrs"]
    rd.close()
    frd = CHK5Reader(files[0])
    fa = frd.info("shard/f/shard-0")["attrs"]
    assert "codec" not in fa and "max_error" in fa["codec_fallback"]
    ia = frd.info("shard/i/shard-0")["attrs"]
    assert "codec" not in ia
    frd.close()
    # raw fallbacks restore bit-exact
    loader = ElasticLoader(files)
    np.testing.assert_array_equal(loader.read_region("i", None), ints)
    np.testing.assert_array_equal(loader.read_region("f", None), wild)
    loader.close()


SUBPROC_COMMON = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys
    sys.path.insert(0, "src")
    from repro.dist.context import make_mesh
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.context import CheckpointConfig, CheckpointContext
    from repro.core.resharding import reshard_tree

    def make_state(mesh):
        state = {"params": {
            "w": jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64),
            "b": jnp.arange(32.0)}, "step": jnp.int32(7)}
        sh = {"params": {"w": NamedSharding(mesh, P("data", "model")),
                         "b": NamedSharding(mesh, P())},
              "step": NamedSharding(mesh, P())}
        return reshard_tree(state, sh)
""")

STORE_CRASH_SCRIPT = SUBPROC_COMMON + textwrap.dedent("""
    ckpt_dir = sys.argv[1]
    mesh = make_mesh((4, 4), ("data", "model"))
    state = make_state(mesh)

    # --- the no-gather Plan guarantee -------------------------------- #
    import repro.core.protect as protect_mod
    import repro.core.pipeline as pipeline_mod
    gathered = []
    real_to_host = protect_mod.to_host
    def spy_to_host(named):
        gathered.extend(named)
        return real_to_host(named)
    protect_mod.to_host = spy_to_host
    pipeline_mod.to_host = spy_to_host

    ctx = CheckpointContext(CheckpointConfig(
        dir=ckpt_dir, backend="fti", dedicated_thread=False))
    ctx.store(state, id=1, level=1)
    # the sharded leaf never went through the host gather, and its Plan
    # snapshot holds per-shard device references, each 1/16 of the leaf
    assert "params/w" not in gathered, gathered
    from repro.core.storage import StoreRequest
    from repro.core.protect import flatten_named
    named, _ = flatten_named(state)
    plan = ctx.tcl.backend.pipeline.plan(StoreRequest(
        named=named, ckpt_id=99, level=1))
    snap = plan.sharded["params/w"]
    assert len(snap.chunks) == 16
    assert all(c.shape == (16, 16) for c in snap.chunks)
    assert all(not isinstance(c.data, np.ndarray) for c in snap.chunks)
    assert "params/w" not in (plan.named_host or {})
    ctx.tcl.backend.pipeline.abort_plan(plan)

    # --- crash after k of n shard files staged ----------------------- #
    import repro.core.resharding as rs
    real_writer = rs.CHK5Writer
    made = []
    class DyingWriter(real_writer):
        def close(self):
            super().close()
            if ".shard" in os.path.basename(self.path):
                made.append(self.path)
                if len(made) == 2:     # k=2 of n staged, then hard kill
                    os._exit(7)
    rs.CHK5Writer = DyingWriter
    state2 = dict(state, step=jnp.int32(8))
    ctx.store(state2, id=2, level=1)   # never returns
    raise SystemExit("store survived the injected crash")
""")

RESTORE_SCRIPT = SUBPROC_COMMON + textwrap.dedent("""
    import glob, io, json, contextlib
    from repro.core.protect import flatten_named
    from repro.tools.chkls import main as chkls_main

    ckpt_dir = sys.argv[1]
    local = os.path.join(ckpt_dir, "node-local", "ckpts")
    # the crashed store left its partial multi-file set staged, uncommitted
    assert os.path.isdir(os.path.join(local, "ckpt-2.tmp"))
    assert not os.path.isdir(os.path.join(local, "ckpt-2"))
    staged = glob.glob(os.path.join(local, "ckpt-2.tmp", "*.shard*.chk5"))
    assert len(staged) >= 2, staged

    # shard inventory of the committed checkpoint via chkls --json
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert chkls_main([os.path.join(local, "ckpt-1", "rank0.chk5"),
                           "--json"]) == 0
    inv = json.loads(buf.getvalue())
    by_name = {d["name"]: d for d in inv["datasets"]}
    idx = by_name["shardidx/params/w"]
    assert idx["attrs"]["n_chunks"] == 16
    assert idx["attrs"]["global_shape"] == [64, 64]
    assert sorted(set(idx["attrs"]["files"])) == [
        f"rank0.shard{j}.chk5" for j in range(4)]
    assert inv["attrs"]["sharded"] is True
    for j in range(4):
        assert os.path.exists(os.path.join(local, "ckpt-1",
                                           f"rank0.shard{j}.chk5"))

    # restore on a different mesh shape — falls back to id 1
    mesh_b = make_mesh((2, 8), ("data", "model"))
    template = make_state(mesh_b)
    template = jax.tree.map(jnp.zeros_like, template)
    ctx = CheckpointContext(CheckpointConfig(
        dir=ckpt_dir, backend="fti", dedicated_thread=False))
    got = ctx.load(template)
    assert ctx.restarted
    ctx.shutdown()
    named = flatten_named(got)[0]
    assert int(named["step"]) == 7          # id 1, not the crashed id 2
    want = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    np.testing.assert_array_equal(np.asarray(named["params/w"]), want)
    print("SHARDED-CRASH-RESTORE-OK")
""")


def test_sharded_store_crash_restore_subprocess(tmp_path):
    """Forced-16-device lane: shard-local store (no gather in Plan), a
    hard kill after 2 of 4 shard files staged, then a fresh process
    restores the previous id on a different mesh and the shard inventory
    checks out via ``chkls --json``."""
    d = str(tmp_path / "ck")
    r = subprocess.run([sys.executable, "-c", STORE_CRASH_SCRIPT, d],
                       capture_output=True, text=True, timeout=540, cwd=".")
    assert r.returncode == 7, r.stdout[-2000:] + r.stderr[-3000:]
    r = subprocess.run([sys.executable, "-c", RESTORE_SCRIPT, d],
                       capture_output=True, text=True, timeout=540, cwd=".")
    assert "SHARDED-CRASH-RESTORE-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
