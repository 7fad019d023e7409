"""Sharding rules need a multi-device mesh → run the assertions in a
subprocess with forced host devices (device count locks at jax init)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys
    sys.path.insert(0, "src")
    from repro.dist.context import make_mesh
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_arch
    from repro.dist.context import use_mesh, resolve_spec, data_axes
    from repro.dist.sharding import param_shardings, batch_sharding
    from repro.models.zoo import build_model
    from jax.tree_util import tree_flatten_with_path
    from repro.dist.sharding import _path_str

    mesh = make_mesh((4, 4), ("data", "model"))

    # 1) divisibility-aware resolve_spec
    assert resolve_spec(mesh, ("model",), (16,)) == P("model")
    assert resolve_spec(mesh, ("model",), (14,)) is None        # 14 % 4 != 0
    assert resolve_spec(mesh, (None, "model"), (3, 8)) == P(None, "model")
    assert resolve_spec(mesh, ("data", "model"), (8, 14)) == P("data", None)

    # 2) param rules: attention/mlp weights sharded on flat feature dims
    cfg = get_arch("mixtral-8x7b")
    m = build_model(cfg)
    ps = param_shardings(mesh, m.param_struct())
    leaves = {(_path_str(p)): s for p, s in
              tree_flatten_with_path(ps)[0]}
    def spec(name):
        return next(v.spec for k, v in leaves.items() if k.endswith(name))
    # stacked layer params carry a leading (n_groups,) dim → leading None
    assert spec("attn/wq") == P(None, None, "model")
    assert spec("attn/wo") == P(None, "model", None)
    # mixtral E=8, 8%4==0 → expert-parallel over E (dim 1 after stack dim)
    assert spec("moe_w_gate") == P(None, "model", None, None)
    assert spec("embed") == P("model", None)      # 32000 % 4 == 0
    assert spec("lm_head") == P(None, "model")

    # 3) granite vocab 49155 NOT divisible → falls to hidden dim
    cfg2 = get_arch("granite-moe-3b-a800m")
    ps2 = param_shardings(mesh, build_model(cfg2).param_struct())
    leaves2 = {(_path_str(p)): s for p, s in tree_flatten_with_path(ps2)[0]}
    emb = next(v.spec for k, v in leaves2.items() if k.endswith("embed"))
    assert emb == P(None, "model"), emb

    # 4) batch sharding folds pod into data on multi-pod meshes
    bs = batch_sharding(mesh, 2)
    assert bs.spec == P("data", None)
    mesh3 = make_mesh((2, 2, 4), ("pod", "data", "model"))
    bs3 = batch_sharding(mesh3, 2)
    assert bs3.spec == P(("pod", "data"), None)

    # 5) shard_hint no-ops without active mesh / disabled hints
    from repro.dist.context import shard_hint, constraint_hints
    x = jnp.ones((8, 8))
    assert shard_hint(x, "data", None) is x      # no active mesh
    with use_mesh(mesh):
        y = shard_hint(x, "data", None)
        assert y is not x
        with constraint_hints(False):
            assert shard_hint(x, "data", None) is x

    # 6) cache shardings: an explicit Protect axis clause pins the batch
    # dim; the size heuristic only covers unmatched leaves.  Ambiguous
    # case: global_batch == n_groups == 4, so the heuristic would shard
    # the layer-stack dim (dim 0) instead of batch (dim 1).
    from repro.core.protect import Protect
    from repro.dist.sharding import cache_shardings
    amb = {"kv": jnp.zeros((4, 8, 4, 64))}    # (n_groups, B=8, heads, dh)
    cs_h = jax.tree.leaves(cache_shardings(mesh, amb, 4))[0]
    assert cs_h.spec == P("data", None, "model", None), cs_h.spec
    cs_e = jax.tree.leaves(cache_shardings(
        mesh, amb, 4, protects=[Protect("**", axis={"batch": 1})]))[0]
    assert cs_e.spec == P(None, "data", None, "model"), cs_e.spec
    # out-of-range explicit dim (cache-union placeholders) → heuristic
    ph = jax.tree.leaves(cache_shardings(
        mesh, {"z": jnp.zeros((0,))}, 4,
        protects=[Protect("**", axis={"batch": 1})]))[0]
    assert ph.spec == P(None) or ph.spec == P(), ph.spec
    # the cache constructors publish the metadata (models/zoo carrier)
    from repro.models.zoo import build_model as bm
    mdl = bm(get_arch("mixtral-8x7b"))
    specs = mdl.cache_protects()
    assert specs and specs[0].axis == {"batch": 1}

    print("SHARDING-OK")
""")


def test_sharding_rules_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420, cwd=".")
    assert "SHARDING-OK" in r.stdout, r.stdout + r.stderr
