"""CP-dedicated thread semantics, data-cursor determinism, elastic restore."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_arch
from repro.core.async_engine import CPDedicatedThread
from repro.core.resharding import ElasticLoader, save_sharded, shard_bounds
from repro.data.synthetic import SyntheticDataset, init_data_state, next_batch


# ------------------------------ async engine ------------------------------ #


def test_async_runs_off_thread():
    cp = CPDedicatedThread()
    tid = {}
    cp.submit(1, lambda: tid.setdefault("worker", threading.get_ident()))
    cp.wait()
    assert tid["worker"] != threading.get_ident()
    cp.shutdown()


def test_async_error_surfaces_later_not_at_submit():
    cp = CPDedicatedThread()

    def boom():
        raise IOError("disk full")

    res = cp.submit(1, boom)
    res.done.wait()
    # FTI semantics: the *next* directive surfaces the failure
    with pytest.raises(RuntimeError, match="disk full"):
        cp.check_errors()
    cp.check_errors()          # cleared after surfacing
    cp.shutdown()


def test_async_inflight_fence():
    cp = CPDedicatedThread(max_inflight=1)
    order = []

    def slow(i):
        def f():
            time.sleep(0.05)
            order.append(i)
        return f

    cp.submit(1, slow(1))
    cp.submit(2, slow(2))      # blocks until 1 finishes (fence)
    cp.wait()
    assert order == [1, 2]
    cp.shutdown()


def test_async_shutdown_drains():
    cp = CPDedicatedThread()
    hits = []
    cp.submit(1, lambda: hits.append(1))
    cp.shutdown()
    assert hits == [1]
    with pytest.raises(RuntimeError):
        cp.submit(2, lambda: None)


# ------------------------------ data cursor ------------------------------- #


def test_cursor_restart_resumes_same_stream():
    cfg = get_arch("tinyllama-1.1b").reduced()
    ds = SyntheticDataset(cfg, 2, 16, seed=7)
    first = [next(ds) for _ in range(3)]
    saved = ds.get_state()
    a = next(ds)
    ds2 = SyntheticDataset(cfg, 2, 16, seed=7)
    ds2.set_state(saved)
    b = next(ds2)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), pos=st.integers(0, 20))
def test_cursor_pure_function(seed, pos):
    cfg = get_arch("tinyllama-1.1b").reduced()
    st0 = init_data_state(seed)
    st0 = st0._replace(position=jnp.int32(pos))
    b1, n1 = next_batch(st0, cfg, 2, 16)
    b2, n2 = next_batch(st0, cfg, 2, 16)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert int(n1.position) == pos + 1


def test_vlm_batch_masks_patch_labels():
    cfg = get_arch("internvl2-1b").reduced()
    b, _ = next_batch(init_data_state(0), cfg, 2, 32)
    p = cfg.n_frontend_tokens
    assert b["labels"].shape == (2, 32)
    assert np.all(np.asarray(b["labels"][:, :p]) == -1)
    assert b["tokens"].shape == (2, 32 - p)


# ---------------------------- elastic restore ----------------------------- #


def _write_shards(tmp_path, world, arrays):
    files = []
    for r in range(world):
        named, offs, gshapes = {}, {}, {}
        for name, arr in arrays.items():
            lo, hi = shard_bounds(arr.shape[0], world, r)
            named[name] = arr[lo:hi]
            offs[name] = lo
            gshapes[name] = list(arr.shape)
        p = str(tmp_path / f"rank{r}.chk5")
        save_sharded(p, named, offs, gshapes, {"world": world})
        files.append(p)
    return files


@settings(max_examples=8, deadline=None)
@given(w1=st.integers(1, 6), w2=st.integers(1, 6),
       rows=st.integers(1, 40), seed=st.integers(0, 100))
def test_elastic_restore_any_world_change(tmp_path_factory, w1, w2, rows, seed):
    tmp = tmp_path_factory.mktemp("el")
    rng = np.random.RandomState(seed)
    arrays = {
        "w": rng.randn(rows, 3).astype(np.float32),
        "m": rng.randn(rows).astype(np.float32),
    }
    files = _write_shards(tmp, w1, arrays)
    loader = ElasticLoader(files)
    for name, arr in arrays.items():
        parts = [loader.read_for_rank(name, w2, r) for r in range(w2)]
        got = np.concatenate(parts, axis=0)
        np.testing.assert_array_equal(got, arr)
    loader.close()


def test_elastic_restore_function(tmp_path):
    from repro.core.resharding import elastic_restore
    arrays = {"w": np.arange(24, dtype=np.float32).reshape(12, 2)}
    _write_shards(tmp_path, 4, arrays)
    got = [elastic_restore(str(tmp_path), 3, r)["w"] for r in range(3)]
    np.testing.assert_array_equal(np.concatenate(got), arrays["w"])
