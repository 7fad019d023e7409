"""Staged pipeline: recovery-ladder order, tier redundancy for every kind
of store (FULL / DIFF / incremental) on every backend, async composition,
and the cross-store digest cache."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends.registry import make_backend
from repro.core.comm import SimulatedCluster
from repro.core.context import CheckpointConfig, CheckpointContext
from repro.core.diff import DiffEngine
from repro.core.storage import CHK_DIFF, CHK_FULL, StorageConfig

WORLD = 4


def _named(rank, val=None):
    return {"w": np.full(256, float(val if val is not None else rank),
                         np.float32),
            "step": np.asarray(np.int32(rank))}


def _backends(tmp_path, name):
    cluster = SimulatedCluster(str(tmp_path / "cluster"), WORLD)
    cfg = StorageConfig(root=str(tmp_path / "shared"), group_size=4,
                        block_bytes=256)
    kw = {"dedicated_thread": False} if name == "fti" else {}
    backends = [make_backend(cfg, c, name, **kw) for c in cluster.comms]
    return cluster, backends


def _store(b, rank, kind, level):
    """One committed checkpoint of `kind` on backend `b` (id of newest)."""
    if kind == "INC":
        inc = b.tcl_store_begin(1, level)
        inc.add({"w": _named(rank)["w"]})
        inc.add({"step": _named(rank)["step"]})
        inc.commit()
        b.tcl_wait()
        return 1
    b.tcl_store(_named(rank), 1, level, CHK_FULL)
    b.tcl_wait()
    if kind == CHK_DIFF:
        named2 = _named(rank)
        named2["w"][3] = -7.0
        b.tcl_store(named2, 2, level, CHK_DIFF)
        b.tcl_wait()
        return 2
    return 1


def test_recovery_ladder_is_l1_to_l4(tmp_path):
    """The read path tries tiers in FTI's ladder order L1→L2→L3→L4, with
    the object store as the final rung (catalog-backed restore — the one
    tier that survives every directory being wiped)."""
    cluster, backends = _backends(tmp_path, "fti")
    names = [t.name for t in backends[0].pipeline.ladder]
    assert names == ["local", "partner", "erasure", "global", "objstore"]
    levels = [t.level for t in backends[0].pipeline.ladder]
    assert levels == sorted(levels) == [1, 2, 3, 4, 5]
    assert backends[0].capabilities()["objstore"] is True


@pytest.mark.parametrize("backend", ["fti", "scr", "veloc"])
@pytest.mark.parametrize("kind", [CHK_FULL, CHK_DIFF, "INC"])
@pytest.mark.parametrize("level", [1, 2, 3, 4])
def test_store_crash_restart_ladder(tmp_path, backend, kind, level):
    """Store → simulated node crash → restart, for every level × backend ×
    store kind; recovery comes from the expected ladder rung."""
    cluster, backends = _backends(tmp_path, backend)
    newest = 0
    for r, b in enumerate(backends):
        newest = _store(b, r, kind, level)

    victim = 1
    # no-crash restore always comes from the ladder's first rung
    named, meta = backends[victim].engine.load_latest()
    assert meta["recovered_via"] == ("global" if level == 4 else "local")

    if level > 1:
        cluster.kill_node(victim)       # L1 alone does not survive this
        got = backends[victim].engine.load_latest()
        assert got is not None, f"L{level} recovery failed after node loss"
        named, meta = got
        assert meta["recovered_via"] == {2: "partner", 3: "erasure",
                                         4: "global"}[level]
    if kind == CHK_DIFF and backend == "fti":
        assert named["w"][3] == -7.0    # diff chain replayed
        assert meta["kind"] == CHK_DIFF
    else:
        assert named["w"][0] == float(victim)
    assert int(named["step"]) == victim
    assert meta["id"] == newest
    for b in backends:
        b.tcl_finalize()


@pytest.mark.parametrize("level", [2, 3])
def test_incremental_gets_level_redundancy(tmp_path, level):
    """Level-2/3 incremental checkpoints are replicated/encoded at commit
    and survive a node loss (routed through the pipeline's Place stage)."""
    cluster, backends = _backends(tmp_path, "fti")
    for r, b in enumerate(backends):
        inc = b.tcl_store_begin(5, level)
        inc.add({"w": np.full(64, float(r), np.float32)})
        rep = inc.commit()
        assert rep is not None and rep.level == level
        b.tcl_wait()
    cluster.kill_node(2)
    got = backends[2].engine.load_latest()
    assert got is not None
    named, meta = got
    assert named["w"][0] == 2.0
    assert meta["incremental"] is True
    assert meta["recovered_via"] == ("partner" if level == 2 else "erasure")


def test_incremental_async_commit_composes(tmp_path):
    """With a CP-dedicated thread, store_begin no longer fences in-flight
    stores, and commit runs Place→Commit asynchronously."""
    cfg = CheckpointConfig(dir=str(tmp_path / "a"), backend="fti",
                           dedicated_thread=True)
    ctx = CheckpointContext(cfg)
    state = {"w": jnp.arange(8.0)}
    ctx.store(state, id=1, level=1)            # async, not waited
    inc = ctx.store_begin(id=2, level=1)       # must not block on store 1
    inc.add({"w": jnp.arange(8.0) + 1})
    assert inc.commit() is None                # async tail → report deferred
    ctx.wait()
    ctx.shutdown()

    ctx2 = CheckpointContext(cfg)
    got = ctx2.load({"w": jnp.zeros(8)})
    assert ctx2.restarted
    np.testing.assert_array_equal(np.asarray(got["w"]), np.arange(8.0) + 1)
    ctx2.shutdown()


def test_async_diff_chain_composes(tmp_path):
    """Back-to-back DIFF stores on the CP thread keep a consistent digest
    chain (Plan runs synchronously in submission order)."""
    cfg = CheckpointConfig(dir=str(tmp_path / "d"), backend="fti",
                           dedicated_thread=True, block_bytes=256,
                           keep_last_full=2)
    ctx = CheckpointContext(cfg)
    x = jnp.arange(4096, dtype=jnp.float32)
    ctx.store({"x": x}, id=1, level=1)                      # FULL
    x2 = x.at[5].set(-1.0)
    ctx.store({"x": x2}, id=2, level=1, kind=CHK_DIFF)      # async DIFF
    x3 = x2.at[900].set(-2.0)
    ctx.store({"x": x3}, id=3, level=1, kind=CHK_DIFF)      # async DIFF
    ctx.wait()
    ctx.shutdown()

    ctx2 = CheckpointContext(CheckpointConfig(dir=str(tmp_path / "d"),
                                              backend="fti"))
    got = ctx2.load({"x": jnp.zeros(4096)})
    assert float(got["x"][5]) == -1.0 and float(got["x"][900]) == -2.0
    ctx2.shutdown()


def test_digest_cache_skips_clean_jax_leaves(monkeypatch):
    """Identical (immutable) jax leaves skip the blockhash kernel on the
    next store; replaced jax leaves and mutable numpy leaves do not."""
    import repro.core.diff as diff_mod
    calls = []
    real = diff_mod.ops.blockhash
    monkeypatch.setattr(diff_mod.ops, "blockhash",
                        lambda leaf, bb: calls.append(1) or real(leaf, bb))

    eng = DiffEngine(block_bytes=256)
    x = jnp.arange(1024, dtype=jnp.float32)
    npb = np.arange(1024, dtype=np.float32)

    eng.update_digests_full({"a": x, "b": npb})
    assert len(calls) == 2

    # same jax object → clean, hash skipped; numpy always re-hashed
    deltas, stats = eng.compute_deltas({"a": x, "b": npb})
    assert len(calls) == 3                       # only "b"
    assert stats.skipped_leaves == 1
    assert stats.dirty_blocks == 0

    # in-place numpy mutation must be caught (no identity shortcut)
    npb[0] = -1.0
    deltas, stats = eng.compute_deltas({"a": x, "b": npb})
    assert len(calls) == 4
    assert stats.dirty_blocks >= 1

    # replaced jax leaf → re-hashed
    y = x.at[0].set(-1.0)
    deltas, stats = eng.compute_deltas({"a": y, "b": npb})
    assert len(calls) == 6
    assert any(d.path == "a" and d.dirty_idx.shape[0] for d in deltas)


def test_full_digest_bookkeeping_moves_to_cp_thread(tmp_path, monkeypatch):
    """FULL stores on diff-capable backends must not pay a synchronous
    full-tree blockhash in Plan: the digest update runs on the CP thread,
    and an interleaved DIFF fences on it (fresh base, no stale digests)."""
    import threading
    import repro.core.diff as diff_mod
    main = threading.get_ident()
    hash_threads = []
    real = diff_mod.ops.blockhash
    monkeypatch.setattr(
        diff_mod.ops, "blockhash",
        lambda leaf, bb: hash_threads.append(threading.get_ident())
        or real(leaf, bb))

    cfg = CheckpointConfig(dir=str(tmp_path / "h"), backend="fti",
                           dedicated_thread=True, block_bytes=256)
    ctx = CheckpointContext(cfg)
    x1 = jnp.arange(4096, dtype=jnp.float32)
    ctx.store({"x": x1}, id=1, level=1)                     # FULL, async
    x2 = x1.at[7].set(-1.0)
    ctx.store({"x": x2}, id=2, level=1, kind=CHK_DIFF)      # interleaved DIFF
    x3 = x2.at[2048].set(-2.0)
    ctx.store({"x": x3}, id=3, level=1)                     # FULL again
    x4 = x3.at[9].set(-3.0)
    ctx.store({"x": x4}, id=4, level=1, kind=CHK_DIFF)
    ctx.wait()
    ctx.shutdown()

    # FULL digests hashed off-thread; DIFF plans hash on the caller (by
    # design) AFTER the fence, so the order is [cp, main, cp, main]
    assert len(hash_threads) == 4
    assert hash_threads[0] != main and hash_threads[2] != main
    assert hash_threads[1] == main and hash_threads[3] == main

    ctx2 = CheckpointContext(CheckpointConfig(dir=str(tmp_path / "h"),
                                              backend="fti"))
    named, meta = ctx2.tcl.backend.engine.load_latest()
    # id=4 committed as a real DIFF link (a stale/missing base would have
    # promoted it to FULL) and the replayed chain carries every mutation
    assert meta["kind"] == CHK_DIFF and meta["id"] == 4
    assert named["x"][7] == -1.0
    assert named["x"][2048] == -2.0
    assert named["x"][9] == -3.0
    ctx2.shutdown()


def test_deferred_error_surfaces_before_digest_mutation(tmp_path):
    """A failed async store must raise at the next directive BEFORE that
    directive's Plan advances the digest chain (and before an incremental
    commit closes its writer) — otherwise later DIFFs diff against data no
    committed checkpoint holds."""
    import shutil
    cfg = CheckpointConfig(dir=str(tmp_path / "e"), backend="fti",
                           dedicated_thread=True, block_bytes=256)
    ctx = CheckpointContext(cfg)
    eng = ctx.tcl.backend.engine
    x = jnp.arange(1024, dtype=jnp.float32)
    ctx.store({"x": x}, id=1, level=1)
    ctx.wait()
    # break the local tier (file where the ckpt tree must go) → async fail
    shutil.rmtree(eng.local_root)
    open(eng.local_root, "w").write("not a dir")
    ctx.store({"x": x.at[0].set(-1.0)}, id=2, level=1, kind=CHK_DIFF)
    ctx.tcl.backend._cp.wait()              # let the failure land
    digests_before = dict(eng.diff._digests)
    with pytest.raises(RuntimeError, match="asynchronous checkpoint"):
        ctx.store({"x": x.at[1].set(-2.0)}, id=3, level=1, kind=CHK_DIFF)
    # the raising directive must not have advanced the digest chain
    assert all(np.array_equal(digests_before[k], v)
               for k, v in eng.diff._digests.items())
    # after the error surfaced, the context keeps working
    os.remove(eng.local_root)
    os.makedirs(eng.local_root)
    ctx.store({"x": x}, id=4, level=4)
    ctx.wait()
    inc = ctx.store_begin(id=5, level=1)
    inc.add({"x": x})
    inc.commit()
    ctx.wait()
    ctx.shutdown()


def test_incremental_commit_retryable_after_deferred_error(tmp_path):
    """check_errors raising inside commit() leaves the store uncommitted
    and retryable."""
    import shutil
    cfg = CheckpointConfig(dir=str(tmp_path / "r"), backend="fti",
                           dedicated_thread=True, block_bytes=256)
    ctx = CheckpointContext(cfg)
    eng = ctx.tcl.backend.engine
    ctx.store({"x": jnp.ones(16)}, id=1, level=1)
    ctx.wait()
    shutil.rmtree(eng.local_root)
    open(eng.local_root, "w").write("not a dir")
    ctx.store({"x": jnp.zeros(16)}, id=2, level=1)      # async fail
    ctx.tcl.backend._cp.wait()
    os.remove(eng.local_root)
    os.makedirs(eng.local_root)
    inc = ctx.store_begin(id=3, level=1)
    inc.add({"w": jnp.ones(4)})
    with pytest.raises(RuntimeError, match="asynchronous checkpoint"):
        inc.commit()
    assert inc.commit() is None             # retry succeeds (async tail)
    ctx.wait()
    ctx.shutdown()


def test_sync_store_failure_invalidates_digest_chain(tmp_path):
    """A synchronous store that fails after Plan advanced the digest chain
    must invalidate it — the next DIFF may not delta against phantom data."""
    import shutil
    cfg = CheckpointConfig(dir=str(tmp_path / "s"), backend="fti",
                           dedicated_thread=False, block_bytes=256)
    ctx = CheckpointContext(cfg)
    eng = ctx.tcl.backend.engine
    x = jnp.arange(1024, dtype=jnp.float32)
    ctx.store({"x": x}, id=1, level=1)
    shutil.rmtree(eng.local_root)
    open(eng.local_root, "w").write("not a dir")
    with pytest.raises(OSError):
        ctx.store({"x": x * -1.0}, id=2, level=1)      # fails mid-Pack
    os.remove(eng.local_root)
    os.makedirs(eng.local_root)
    # digest base is gone → this DIFF promotes to FULL instead of emitting
    # a delta against the never-committed id=2 content
    rep = ctx.store({"x": x.at[0].set(5.0)}, id=3, level=1, kind=CHK_DIFF)
    assert rep.kind == CHK_FULL and rep.promoted_full
    ctx.shutdown()
    ctx2 = CheckpointContext(CheckpointConfig(dir=str(tmp_path / "s"),
                                              backend="fti"))
    got = ctx2.load({"x": jnp.zeros(1024)})
    assert float(got["x"][0]) == 5.0 and float(got["x"][1]) == 1.0
    ctx2.shutdown()


def test_shutdown_surfaces_final_async_error(tmp_path):
    """A failure in the very last async store must not vanish at shutdown."""
    import shutil
    ctx = CheckpointContext(CheckpointConfig(dir=str(tmp_path / "f"),
                                             backend="fti",
                                             dedicated_thread=True))
    eng = ctx.tcl.backend.engine
    shutil.rmtree(eng.local_root)
    open(eng.local_root, "w").write("not a dir")
    ctx.store({"x": jnp.ones(4)}, id=1, level=1)    # async, will fail
    with pytest.raises(RuntimeError, match="asynchronous checkpoint"):
        ctx.shutdown()


def test_config_dedicated_thread_reaches_veloc(tmp_path):
    """dedicated_thread=False in the user config must make VeloC
    synchronous too, not just FTI."""
    ctx = CheckpointContext(CheckpointConfig(dir=str(tmp_path / "v"),
                                             backend="veloc",
                                             dedicated_thread=False))
    assert ctx.tcl.backend._cp is None
    rep = ctx.store({"x": jnp.ones(8)}, id=1, level=1)
    assert rep is not None and rep.kind == CHK_FULL    # sync → report now
    ctx.shutdown()


def test_backend_capabilities_and_shared_stacks(tmp_path):
    """Backends declare capabilities and compose the shared tier stacks —
    none re-implements placement."""
    from repro.core.comm import LocalComm
    caps = {}
    for name in ("fti", "scr", "veloc"):
        b = make_backend(StorageConfig(root=str(tmp_path / name)),
                         LocalComm(str(tmp_path / name / "nl")), name)
        caps[name] = b.capabilities()
        assert sorted(b.pipeline.stacks) == [1, 2, 3, 4]
        assert [t.name for t in b.pipeline.stacks[3]] == ["local", "erasure"]
        b.tcl_finalize()
    assert caps["fti"]["diff"] and not caps["scr"]["diff"]
    assert caps["veloc"]["dedicated_thread"]
    assert not caps["scr"]["dedicated_thread"]


# ------------------------------------------------------------------ #
# deferred DIFF payloads: Plan starts the packed copies, Pack reads them
# ------------------------------------------------------------------ #


@pytest.mark.parametrize("dedicated_thread", [True, False])
def test_diff_store_with_deferred_payload_roundtrips(tmp_path,
                                                     dedicated_thread):
    """A DIFF store restores bit-exactly whether Pack reads its payload on
    the CP thread (copy overlapped) or right after Plan (synchronous)."""
    cfg = CheckpointConfig(dir=str(tmp_path / "p"), backend="fti",
                           dedicated_thread=dedicated_thread, block_bytes=256)
    ctx = CheckpointContext(cfg)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4096).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 255, 3000).astype(np.uint8))
    ctx.store({"x": x, "y": y}, id=1, level=1)
    x2 = x.at[5].set(-1.0).at[700].set(2.5).at[3000].set(7.0)
    y2 = y.at[2999].set(0)
    ctx.store({"x": x2, "y": y2}, id=2, level=1, kind=CHK_DIFF)
    ctx.wait()
    ctx.shutdown()

    ctx2 = CheckpointContext(CheckpointConfig(dir=str(tmp_path / "p"),
                                              backend="fti"))
    named, meta = ctx2.tcl.backend.engine.load_latest()
    assert meta["kind"] == CHK_DIFF and meta["id"] == 2
    assert named["x"].tobytes() == np.asarray(x2).tobytes()
    assert named["y"].tobytes() == np.asarray(y2).tobytes()
    ctx2.shutdown()


def _planned_diff(tmp_path):
    """A synchronous FTI backend with a committed FULL base, and the Plan
    of a DIFF store over it whose packed copies are still in flight."""
    from repro.core.pipeline import StoreRequest
    cfg = StorageConfig(root=str(tmp_path / "shared"), block_bytes=256)
    cluster = SimulatedCluster(str(tmp_path / "cluster"), 1)
    b = make_backend(cfg, cluster.comms[0], "fti", dedicated_thread=False)
    x = jnp.arange(4096, dtype=jnp.float32)
    b.tcl_store({"x": x}, 1, 1, CHK_FULL)
    pipe = b.pipeline
    plan = pipe.plan(StoreRequest(named={"x": x.at[9].set(-1.0)}, ckpt_id=2,
                                  level=1, kind=CHK_DIFF))
    assert plan.kind == CHK_DIFF and plan.deltas[0].in_flight_bytes
    return pipe, plan


def test_failed_diff_tail_invalidates_and_drops_device_payload(tmp_path):
    """A DIFF tail refused by the epoch guard (a store planned before it
    failed) invalidates its leaves and drops its deltas, so no packed
    device buffer outlives the failure."""
    import gc
    import weakref
    pipe, plan = _planned_diff(tmp_path)
    packs = [weakref.ref(d.blocks) for d in plan.deltas]
    pipe.diff.invalidate(["elsewhere"])           # a failed earlier store
    with pytest.raises(RuntimeError, match="digest base invalidated"):
        pipe.finish(plan)
    assert plan.deltas is None
    assert "x" not in pipe.diff._digests          # the chain forgot x
    gc.collect()
    assert all(r() is None for r in packs)


def test_aborted_diff_plan_drops_device_payload(tmp_path):
    import gc
    import weakref
    pipe, plan = _planned_diff(tmp_path)
    packs = [weakref.ref(d.blocks) for d in plan.deltas]
    pipe.abort_plan(plan)
    assert plan.deltas is None
    gc.collect()
    assert all(r() is None for r in packs)


def test_promoted_diff_never_packs(tmp_path, monkeypatch):
    """Above the break-even dirty ratio the store promotes to FULL from
    the digests alone: the diffpack kernel never runs."""
    import repro.core.diff as diff_mod
    calls = []
    monkeypatch.setattr(diff_mod.ops, "pack_dirty",
                        lambda *a, **k: calls.append(1))
    cfg = CheckpointConfig(dir=str(tmp_path / "pr"), backend="fti",
                           dedicated_thread=False, block_bytes=256)
    ctx = CheckpointContext(cfg)
    x = jnp.arange(4096, dtype=jnp.float32)
    ctx.store({"x": x}, id=1, level=1)
    rep = ctx.store({"x": x + 1.0}, id=2, level=1, kind=CHK_DIFF)
    assert rep.promoted_full and rep.kind == CHK_FULL and not calls
    ctx.shutdown()
