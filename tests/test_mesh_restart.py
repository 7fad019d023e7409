"""Mesh-change restart: a checkpoint written under one mesh restores under
different mesh shapes, bit-exact, with the restored leaves placed per the
new mesh's shardings.  Stores are shard-local (no full-tree gather): each
leaf's owned shards land as ``shard-<k>`` datasets in sibling
``rank<r>.shard<j>.chk5`` files, and restore assembles exactly the regions
each target device needs via the ElasticLoader path — on all three
backends.  Runs in subprocesses with 16 forced host devices (device count
locks at jax init)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import glob
    import sys
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, "src")
    from repro.dist.context import make_mesh
    from repro.configs import get_arch
    from repro.core.context import CHK_DIFF, CheckpointConfig, CheckpointContext
    from repro.core.protect import flatten_named
    from repro.core.resharding import ElasticLoader, gather_tree, reshard_tree
    from repro.dist.sharding import param_shardings
    from repro.models.zoo import build_model

    ckpt_dir = sys.argv[1]
    backend = sys.argv[2]
    diff_link = backend == "fti"        # only fti has checkpoint kinds
    cfg = get_arch("tinyllama-1.1b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))

    # store under a 4x4 mesh, params sharded per the TP/DP rules
    mesh_a = make_mesh((4, 4), ("data", "model"))
    params_a = reshard_tree(params, param_shardings(mesh_a, m.param_struct()))
    ctx = CheckpointContext(CheckpointConfig(
        dir=ckpt_dir, backend=backend, dedicated_thread=False,
        block_bytes=256))
    ctx.store(params_a, id=1, level=1)                       # FULL base
    embed2 = params_a["embed"].at[0, 0].set(-3.0)            # stays sharded
    params_a2 = dict(params_a, embed=embed2)
    ctx.store(params_a2, id=2, level=1,
              kind=CHK_DIFF if diff_link else "FULL")
    ctx.shutdown()
    want = gather_tree(params_a2)                            # global view

    # the store was shard-local: shard files sit next to the container,
    # and ElasticLoader assembles any region of a leaf straight from them
    ck1 = os.path.join(ckpt_dir, "node-local", "ckpts", "ckpt-1")
    shard_files = sorted(glob.glob(os.path.join(ck1, "rank0.shard*.chk5")))
    assert shard_files, os.listdir(ck1)
    loader = ElasticLoader(shard_files)
    assert "embed" in loader.names(), loader.names()
    g = loader.global_shape("embed")
    region = loader.read_region(
        "embed", (slice(1, g[0] // 2), slice(0, g[1])))
    base_embed = np.asarray(gather_tree({"e": params_a})["e"]["embed"])
    np.testing.assert_array_equal(region, base_embed[1:g[0] // 2])
    loader.close()

    # restart on two other mesh shapes: the restart template carries the
    # new mesh's shardings; load must land every leaf on them, bit-exact
    for shape in ((2, 8), (16, 1)):
        mesh_b = make_mesh(shape, ("data", "model"))
        sh_b = param_shardings(mesh_b, m.param_struct())
        template = reshard_tree(jax.tree.map(jnp.zeros_like, params), sh_b)
        ctx2 = CheckpointContext(CheckpointConfig(
            dir=ckpt_dir, backend=backend, dedicated_thread=False,
            block_bytes=256))
        got = ctx2.load(template)
        assert ctx2.restarted, shape
        ctx2.shutdown()
        got_named = flatten_named(got)[0]
        sh_named = flatten_named(sh_b)[0]
        for path, arr in flatten_named(want)[0].items():
            np.testing.assert_array_equal(
                np.asarray(got_named[path]), arr, err_msg=f"{shape} {path}")
            assert got_named[path].sharding == sh_named[path], (shape, path)
    if diff_link:
        assert float(want["embed"][0, 0]) == -3.0   # the DIFF link replayed
    print("MESH-RESTART-OK")
""")


def test_store_one_mesh_restore_on_two_others(tmp_path):
    r = subprocess.run([sys.executable, "-c", SCRIPT,
                        str(tmp_path / "ck"), "fti"],
                       capture_output=True, text=True, timeout=540, cwd=".")
    assert "MESH-RESTART-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_mesh_restore_from_shard_files_scr_veloc(tmp_path):
    """The same store → mesh-change restore cycle through the other two
    backends (file-mode SCR writes the identical sharded layout; VeloC
    exercises the shared pipeline pack)."""
    for backend in ("scr", "veloc"):
        r = subprocess.run([sys.executable, "-c", SCRIPT,
                            str(tmp_path / f"ck-{backend}"), backend],
                           capture_output=True, text=True, timeout=540,
                           cwd=".")
        assert "MESH-RESTART-OK" in r.stdout, \
            backend + ": " + r.stdout[-2000:] + r.stderr[-3000:]
