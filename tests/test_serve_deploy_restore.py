"""Checkpoint-as-deployment across a mesh change, on a forced-16-device
host: a training process stores two FULL level-4 checkpoints from a 4×4
mesh (v2 a fine-tune of v1 touching one small leaf), then a fresh serving
process on a **1×8 mesh** follows the catalog with :class:`FleetDeployer`
— the params are assembled directly onto the serving mesh (shard region
reads, no global host array), the v1→v2 rollout pulls only the chunk
delta (<30% of the full weight bytes, matching ``CatalogView.diff``'s
prediction), and the installed tree is bit-exact with the trained one."""

import subprocess
import sys
import textwrap

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

    def orig_arrays(tuned=False):
        # w is small and fully retuned between v1 and v2; c is large and
        # untouched — the chunk delta of the publish is w's bytes only
        rng = np.random.default_rng(0)
        w = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
        c = rng.normal(size=(256, 256)).astype(np.float32)
        if tuned:
            w = w * 1.25 + 3.0
        return w, c

    def make_state(mesh, tuned=False):
        w, c = orig_arrays(tuned)
        state = {"params": {"w": jnp.asarray(w), "c": jnp.asarray(c)},
                 "step": jnp.int32(2 if tuned else 1)}
        sh = {"params": {"w": NamedSharding(mesh, P("data", "model")),
                         "c": NamedSharding(mesh, P("data", "model"))},
              "step": NamedSharding(mesh, P())}
        return reshard_tree(state, sh)

    def make_ctx(ckpt_dir):
        return CheckpointContext(CheckpointConfig(
            dir=ckpt_dir, backend="fti", dedicated_thread=False,
            objstore_cdc_min_bytes=512, objstore_cdc_avg_bytes=2048,
            objstore_cdc_max_bytes=8192))
""")

TRAIN_SCRIPT = SUBPROC_COMMON + textwrap.dedent("""
    ckpt_dir = sys.argv[1]
    mesh = make_mesh((4, 4), ("data", "model"))
    ctx = make_ctx(ckpt_dir)
    ctx.store(make_state(mesh), id=1, level=4)
    ctx.store(make_state(mesh, tuned=True), id=2, level=4)
    ctx.shutdown()

    from repro.objstore.inspect import CatalogView
    view = CatalogView.from_root(os.path.join(ckpt_dir, "objstore"))
    assert view.ids() == [1, 2], view.ids()
    e1, e2 = view.entry(1), view.entry(2)
    assert e1.kind == "FULL" and e2.kind == "FULL"
    assert [f for f in e2.rank_files(0) if ".shard" in f.name], \\
        [f.name for f in e2.files]
    # the catalog already predicts a small publish: only w's chunks moved
    d = CatalogView.diff(e1, e2)
    assert 0 < d.ratio < 0.30, (d.bytes_delta, d.bytes_total)
    print("TRAIN-PUBLISH-OK")
""")

SERVE_SCRIPT = SUBPROC_COMMON + textwrap.dedent("""
    from repro.objstore.client import make_object_store
    from repro.objstore.inspect import CatalogView
    from repro.serve.deploy import FleetDeployer, Replica
    from repro.serve.engine import ServingEngine, WeightsHandle

    ckpt_dir = sys.argv[1]
    store = make_object_store("file:" + os.path.join(ckpt_dir, "objstore"))

    # the serving mesh is a *different* factorization of different size
    # (8 of the 16 devices) — deploy must land the 4x4-trained shards on it
    mesh_b = make_mesh((1, 8), ("data", "model"))
    sh = NamedSharding(mesh_b, P("data", "model"))
    template = {"w": jax.device_put(jnp.zeros((64, 64), jnp.float32), sh),
                "c": jax.device_put(jnp.zeros((256, 256), jnp.float32), sh)}

    class _M:  # the engine only touches .decode_step at construction
        def decode_step(self, params, tok, caches, pos):
            return tok.astype(jnp.float32)[:, :, None], caches

    eng = ServingEngine(_M(), WeightsHandle(params=template),
                        batch=2, max_len=8)
    rep = Replica(name="serve0", engine=eng,
                  cache_root=os.path.join(ckpt_dir, "serve-cache"),
                  prefix="params")

    # the replica previously deployed v1 — its chunk cache is warm
    view = CatalogView.from_store(store)
    e1, e2 = view.entry(1), view.entry(2)
    rep.puller(store).pull(e1)

    dep = FleetDeployer(store, [rep])
    last = dep.run_until_converged()
    assert last == {"action": "converged", "entry": 2}, last
    assert eng.weights.entry_id == 2 and eng.weights.epoch >= 1

    # the v1 -> v2 rollout pulled only the chunk delta, and the measured
    # bytes agree with the catalog-level prediction
    fetched = dep.stats["bytes_fetched"]
    cached = dep.stats["bytes_cached"]
    assert cached > 0 and fetched + cached > 0
    measured = fetched / float(fetched + cached)
    predicted = CatalogView.diff(e1, e2).ratio
    assert measured < 0.30, (fetched, cached)
    assert abs(measured - predicted) < 0.10, (measured, predicted)

    # bit-exact across the mesh change, assembled onto the serve sharding
    w2, c2 = orig_arrays(tuned=True)
    np.testing.assert_array_equal(np.asarray(eng.params["w"]), w2)
    np.testing.assert_array_equal(np.asarray(eng.params["c"]), c2)
    assert eng.params["w"].sharding.is_equivalent_to(sh, 2)
    assert eng.params["c"].sharding.is_equivalent_to(sh, 2)
    print("SERVE-DEPLOY-RESHARD-OK")
""")


def test_serve_deploy_train_4x4_serve_1x8(tmp_path):
    """Forced-16-device lane: 4×4 training store → 1×8 serving fleet
    hot-swap — chunk-delta pull, bit-exact params, serve-mesh sharding."""
    d = str(tmp_path / "ck")
    r = subprocess.run([sys.executable, "-c", TRAIN_SCRIPT, d],
                       capture_output=True, text=True, timeout=540, cwd=".")
    assert "TRAIN-PUBLISH-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
    r = subprocess.run([sys.executable, "-c", SERVE_SCRIPT, d],
                       capture_output=True, text=True, timeout=540, cwd=".")
    assert "SERVE-DEPLOY-RESHARD-OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
