"""Sequence-sharded KV decode (long-context path): GSPMD's partial-softmax
combine must be numerically identical to single-device decode. Runs in a
subprocess with 8 forced host devices."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    from repro.dist.context import make_mesh
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch
    from repro.dist.context import use_mesh
    from repro.models.zoo import build_model

    cfg = get_arch("tinyllama-1.1b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))

    # build a warm cache by decoding 16 tokens on one device
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 17), 0,
                              cfg.vocab_size, jnp.int32)
    caches = m.init_caches(1, 64)
    for i in range(16):
        ref_logits, caches = m.decode_step(params, toks[:, i:i+1], caches,
                                           jnp.int32(i))
    ref_logits, ref_caches = m.decode_step(params, toks[:, 16:17], caches,
                                           jnp.int32(16))

    # now the same step with the KV cache sequence-sharded over 8 devices
    mesh = make_mesh((8, 1), ("data", "model"))
    def shard_cache(leaf):
        # (L, B, C, KV, dh): shard the cache-seq dim (64 % 8 == 0)
        dims = [None] * leaf.ndim
        if leaf.ndim >= 3 and leaf.shape[2] == 64:
            dims[2] = "data"
        return NamedSharding(mesh, P(*dims))
    with use_mesh(mesh):
        cshard = jax.tree.map(shard_cache, caches)
        caches_sharded = jax.tree.map(
            lambda x, s: jax.device_put(x, s), caches, cshard)
        step = jax.jit(m.decode_step,
                       in_shardings=(None, None, cshard, None),
                       out_shardings=(NamedSharding(mesh, P()), cshard))
        got_logits, _ = step(params, toks[:, 16:17], caches_sharded,
                             jnp.int32(16))
        txt = step.lower(params, toks[:, 16:17], caches_sharded,
                         jnp.int32(16)).compile().as_text()

    # bf16 activations + different reduction order across shards ⇒ a few
    # ulps of bf16 at logit scale (~0.003 abs)
    np.testing.assert_allclose(
        np.asarray(got_logits, np.float32),
        np.asarray(ref_logits, np.float32), rtol=5e-2, atol=2e-2)
    # the combine must be reductions (all-reduce), not a 64-token gather
    n_ar = txt.count(" all-reduce(") + txt.count(" all-reduce-start(")
    assert n_ar > 0, "expected partial-softmax all-reduces"
    print("DIST-DECODE-OK all_reduces=", n_ar)
""")


def test_seq_sharded_decode_matches_single_device():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=540, cwd=".")
    assert "DIST-DECODE-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
