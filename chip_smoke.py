"""Smoke test of the checkpointed training and serving path on a TPU.

Drives the main path once through the entry points a user calls, at the
published widths of tinyllama-1.1b with random weights made from a seed:

  (a) train   ``launch/train.py``'s worker, depth cut to fit one chip, async
              FULL saves every 2 steps at levels 1, 2 and 4 on the default
              ``fti`` backend; rerun into the same directory, which must
              resume with the last stored state bit for bit.
  (b) diff    FULL-store the params, change one layer's blocks (the
              fine-tune case), DIFF-store them (device blockhash + diffpack)
              and restore them bit for bit in a fresh context.
  (c) serve   ``launch/serve.py --full`` at all 22 layers, killed after 16
              of 32 tokens and rerun: it resumes and ends on the same
              tokens and decode state as an uninterrupted run.

    python3 chip_smoke.py                # (a)-(c) on one chip
    python3 chip_smoke.py --four-chips   # (d) only, on four chips

  (d) four chips: the phase-(a) state sharded on a 2x2 (data, model) mesh,
      FULL-stored asynchronously from shard-local snapshots, restored onto
      a 4x1 mesh, then a DIFF store of a sharded leaf restored the same
      way; every device must hold exactly its region.

Every phase runs in this one process (a chip belongs to one process).  The
script exits non-zero on any failure, and at once when JAX finds no TPU:
no phase ever runs on the CPU.  Its working files live in ``.chip_smoke/``
next to it and are removed at the end.  Earlier stdout lines give each
phase's chip wall and compile seconds; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
ARCH = "tinyllama-1.1b"
#: layers kept of tinyllama's 22 for training on one 16 GiB chip: the step
#: is not donated and the deferred digest job holds the previous state, so
#: ~28 B per parameter (params + Adam m, v twice, f32 grads) plus
#: activations — 6 layers are ~395 M parameters, ~11 GB
TRAIN_LAYERS = 6
TRAIN_STEPS = 6
SAVE_EVERY = 2


class _Tee(io.TextIOBase):
    """Write-through to the real stdout while keeping a copy to check."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def logged(fn, *args, **kwargs):
    """→ (fn's result, everything it printed)."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        result = fn(*args, **kwargs)
    return result, tee.buf.getvalue()


class Phase:
    """Times one phase on the host clock (around work that ended in a host
    copy or a blocking wait) and sums XLA backend-compile seconds."""

    compile_s = 0.0

    @classmethod
    def listen(cls):
        import jax

        def on_event(event, duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compile_s += duration
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compile_s
        print(f"[smoke] phase {self.name} ...", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            print(f"[smoke] phase {self.name}: chip wall seconds "
                  f"{time.perf_counter() - self.t0:.2f}, compile seconds "
                  f"{Phase.compile_s - self.c0:.2f}", flush=True)


def require(cond, what):
    if not cond:
        raise AssertionError(what)


def host_tree(tree):
    import jax
    return jax.device_get(tree)


def assert_bit_equal(got, want, what):
    """Leaf-by-leaf byte equality of two host trees (NaN-safe)."""
    import jax
    import numpy as np
    paths_got = jax.tree_util.tree_leaves_with_path(got)
    leaves_want = jax.tree_util.tree_leaves(want)
    require(len(paths_got) == len(leaves_want),
            f"{what}: {len(paths_got)} leaves != {len(leaves_want)}")
    for (path, g), w in zip(paths_got, leaves_want):
        g, w = np.asarray(g), np.asarray(w)
        require(g.dtype == w.dtype and g.shape == w.shape
                and g.tobytes() == w.tobytes(),
                f"{what}: leaf {jax.tree_util.keystr(path)} differs")


def assert_on_devices(tree, platform, what):
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        require(isinstance(leaf, jax.Array), f"{what}: a leaf is not on "
                f"the device ({type(leaf).__name__})")
        require(all(d.platform == platform for d in leaf.devices()),
                f"{what}: a leaf is not on {platform}")


def train_config(layers=TRAIN_LAYERS):
    from repro.configs import get_arch
    cfg = get_arch(ARCH)
    return dataclasses.replace(cfg, n_layers=min(layers, cfg.n_layers))


def committed(ckpt_dir):
    """{ckpt id: manifest} over the local and global tiers."""
    from repro.core import manifest as mf
    out = {}
    for root in (os.path.join(ckpt_dir, "node-local", "ckpts"),
                 os.path.join(ckpt_dir, "global")):
        for i in mf.list_committed(root):
            out.setdefault(i, mf.read_manifest(root, i))
    return out


def newest_stored(ckpt_dir):
    """(id, {leaf name: host array}) of the newest checkpoint in a dir."""
    from repro.core.context import CheckpointConfig, CheckpointContext
    ctx = CheckpointContext(CheckpointConfig(dir=ckpt_dir, backend="fti"))
    try:
        named, meta = ctx.tcl.backend.engine.load_latest()
    finally:
        ctx.shutdown()
    return meta["id"], named


# --------------------------------------------------------------------------- #
# (a) train: async FULL saves, then a resume bit for bit
# --------------------------------------------------------------------------- #


def phase_train(work, cfg, *, batch=8, seq=128, platform="tpu"):
    from repro.launch import train
    from repro.train.loop import LevelSchedule

    d = os.path.join(work, "train")
    argv = ["--arch", ARCH, "--ckpt-dir", d, "--backend", "fti",
            "--steps", str(TRAIN_STEPS), "--ckpt-every", str(SAVE_EVERY),
            "--batch", str(batch), "--seq", str(seq), "--seed", "0"]
    # checkpoint k of the run goes to level 1, 2, 4 for k = 1, 2, 3
    levels = LevelSchedule(l1_every=1, l2_every=2, l3_every=0, l4_every=3)
    want_levels = {SAVE_EVERY * k: lv for k, lv in ((1, 1), (2, 2), (3, 4))}

    with Phase("train"):
        summary, _ = logged(train.worker, train.parse_args(argv), cfg=cfg,
                            levels=levels)
        require(not summary["restarted"], "first run found a checkpoint")
        require(summary["stats"]["stores"] == len(want_levels),
                f"stores committed: {summary['stats']}")
        stored = host_tree(summary["state"])
        del summary
        man = committed(d)
        last = max(want_levels)
        require(last in man, f"last store {last} not committed: {sorted(man)}")
        for i, m in man.items():
            require(m.get("kind") == "FULL" and m.get("level") ==
                    want_levels.get(i), f"checkpoint {i} manifest {m}")
        print(f"[smoke] train: {len(want_levels)} async FULL stores "
              f"committed at levels {sorted(set(want_levels.values()))}; "
              f"manifests on disk for ids {sorted(man)}", flush=True)

    with Phase("train-resume"):
        summary, out = logged(train.worker, train.parse_args(argv), cfg=cfg,
                              levels=levels)
        require(f"resuming from step {last}" in out, "rerun did not log "
                "the resume")
        require(summary["restarted"], "rerun did not restart")
        assert_on_devices(summary["state"], platform, "resumed state")
        assert_bit_equal(host_tree(summary["state"]), stored,
                         "resumed vs stored train state")
        del summary
        print(f"[smoke] train: resumed from step {last}, state bit-exact",
              flush=True)
    return stored


# --------------------------------------------------------------------------- #
# (b) DIFF: one changed layer through the device blockhash + diffpack
# --------------------------------------------------------------------------- #


def changed_layer(params, layer):
    """The fine-tune case: only layer ``layer`` of the stacked blocks
    changes; embeddings and the head stay the same arrays."""
    import jax

    def bump(path, leaf):
        if "groups" in jax.tree_util.keystr(path):
            return leaf.at[layer].add(leaf.dtype.type(1e-3))
        return leaf
    return jax.tree_util.tree_map_with_path(bump, params)


def phase_diff(work, cfg, *, platform="tpu"):
    import jax
    from repro.core.context import (CHK_DIFF, CheckpointConfig,
                                    CheckpointContext)
    from repro.kernels import ops
    from repro.models.zoo import build_model

    d = os.path.join(work, "diff")
    with Phase("diff"):
        model = build_model(cfg)
        base = model.init(jax.random.PRNGKey(7))
        if platform == "tpu":
            require(ops._use_pallas(), "Pallas dispatch is off on the chip")
            leaf = base["embed"]
            text = ops.blockhash_pallas.lower(
                leaf, ops.DEFAULT_BLOCK_BYTES, ops.row_mesh(leaf)
            ).compile().as_text()
            require("tpu_custom_call" in text, "blockhash is not the kernel")
        reports = []
        ctx = CheckpointContext(CheckpointConfig(dir=d, backend="fti"))
        ctx.observe_store_reports(reports.append)
        ctx.store(base, id=1, level=1)
        tuned = changed_layer(base, layer=cfg.n_layers // 2)
        ctx.store(tuned, id=2, level=1, kind=CHK_DIFF)
        ctx.wait()
        ctx.shutdown()
        require([r.ckpt_id for r in reports] == [1, 2],
                f"stores committed: {[r.ckpt_id for r in reports]}")
        rep = reports[1]
        require(rep.kind == "DIFF" and not rep.promoted_full,
                f"DIFF store promoted: {rep}")
        require(rep.dirty_ratio is not None and rep.dirty_ratio < 0.5,
                f"dirty ratio {rep.dirty_ratio}")
        want = host_tree(tuned)
        del base, tuned

        fresh = CheckpointContext(CheckpointConfig(dir=d, backend="fti"))
        got = fresh.load(jax.eval_shape(model.init, jax.random.PRNGKey(7)))
        fresh.shutdown()
        require(fresh.restarted, "no checkpoint restored")
        assert_bit_equal(host_tree(got), want, "restored vs DIFF-stored")
        del got
        print(f"[smoke] diff: dirty ratio {rep.dirty_ratio:.4f}, "
              f"{rep.bytes_payload} payload bytes, restore bit-exact",
              flush=True)


# --------------------------------------------------------------------------- #
# (c) serve: kill after 16 tokens, resume, same tokens as uninterrupted
# --------------------------------------------------------------------------- #


def phase_serve(work, *, full=True):
    from repro.launch import serve

    def run(d, *extra):
        argv = ["--arch", ARCH, "--batch", "4", "--prompt-len", "16",
                "--gen", "32", "--ckpt-dir", d, "--backend", "fti", *extra]
        return logged(serve.main, argv + (["--full"] if full else []))

    def last_tokens(out):
        lines = [ln for ln in out.splitlines() if "last 16:" in ln]
        require(len(lines) == 1, "serve printed no tokens")
        return lines[0].split("last 16:", 1)[1].strip()

    with Phase("serve"):
        d, ref_d = os.path.join(work, "serve"), os.path.join(work, "serve-ref")
        rc, _ = run(d, "--kill-after", "16")
        require(rc == 39, f"killed serve run exited {rc}")
        rc, out = run(d)
        require(rc == 0, f"resumed serve run exited {rc}")
        require("resumed at pos 32" in out, "serve rerun did not resume")
        resumed = last_tokens(out)
        rc, out = run(ref_d)
        require(rc == 0, f"uninterrupted serve run exited {rc}")
        require(last_tokens(out) == resumed,
                "resumed tokens differ from an uninterrupted run")
        # random weights may decode one token over and over, so the tokens
        # alone prove little: the decode state each run stored last (KV
        # cache, position) must match bit for bit
        got_id, got = newest_stored(d)
        want_id, want = newest_stored(ref_d)
        require(got_id == want_id == 48 and got.keys() == want.keys(),
                f"last decode states: ids {got_id}, {want_id}")
        assert_bit_equal(got, want, "resumed vs uninterrupted decode state")
        print("[smoke] serve: resumed at pos 32; the last 16 tokens and the "
              "decode state at pos 48 match an uninterrupted run bit for "
              "bit", flush=True)


# --------------------------------------------------------------------------- #
# (d) four chips: 2x2 sharded store -> 4x1 restore, FULL and DIFF
# --------------------------------------------------------------------------- #


def sharded_train_state(cfg, mesh):
    import jax
    from repro.data.synthetic import init_data_state
    from repro.dist.sharding import param_shardings
    from repro.models.zoo import build_model
    from repro.train.state import init_train_state

    def init():
        model = build_model(cfg)
        return init_train_state(model.init(jax.random.PRNGKey(0)),
                                jax.random.PRNGKey(1), init_data_state(0))
    shardings = param_shardings(mesh, jax.eval_shape(init))
    return jax.jit(init, out_shardings=shardings)(), shardings


def assert_regions(tree, want, shardings, what):
    """Every device of the target sharding holds exactly its region."""
    import jax
    import numpy as np
    for (path, leaf), w, sh in zip(
            jax.tree_util.tree_leaves_with_path(tree),
            jax.tree_util.tree_leaves(want),
            jax.tree_util.tree_leaves(shardings)):
        name = jax.tree_util.keystr(path)
        require(leaf.sharding.is_equivalent_to(sh, leaf.ndim),
                f"{what}: {name} landed as {leaf.sharding}")
        held = {s.device for s in leaf.addressable_shards}
        require(held == set(sh.mesh.devices.flat),
                f"{what}: {name} is on {len(held)} of "
                f"{sh.mesh.devices.size} devices")
        for s in leaf.addressable_shards:
            require(np.asarray(s.data).tobytes()
                    == np.asarray(w)[s.index].tobytes(),
                    f"{what}: {name} region on {s.device} differs")


def phase_four(work, cfg):
    import jax
    from repro.core.context import (CHK_DIFF, CheckpointConfig,
                                    CheckpointContext)
    from repro.dist.context import make_mesh

    devices = jax.devices()
    require(len(devices) == 4, f"--four-chips needs 4 devices, has "
            f"{len(devices)}")
    d = os.path.join(work, "four")
    mesh_a = make_mesh((2, 2), ("data", "model"), devices=devices)
    mesh_b = make_mesh((4, 1), ("data", "model"), devices=devices)

    with Phase("four-chips-full"):
        state, _ = sharded_train_state(cfg, mesh_a)
        stored = host_tree(state)
        reports = []
        ctx = CheckpointContext(CheckpointConfig(dir=d, backend="fti"))
        ctx.observe_store_reports(reports.append)
        ctx.store(state, id=1, level=1)
        ctx.wait()
        require([r.ckpt_id for r in reports] == [1], "FULL store lost")

        template, shard_b = sharded_train_state(cfg, mesh_b)
        fresh = CheckpointContext(CheckpointConfig(dir=d, backend="fti"))
        got = fresh.load(template)
        fresh.shutdown()
        del template
        require(fresh.restarted, "no checkpoint restored on 4x1")
        assert_bit_equal(host_tree(got), stored, "4x1 restore vs 2x2 store")
        assert_regions(got, stored, shard_b, "4x1 restore")
        del got
        print("[smoke] four chips: 2x2 sharded FULL store restored onto "
              "4x1 bit-exact, every device holds its region", flush=True)

    with Phase("four-chips-diff"):
        # one sharded leaf changes in its first rows: a DIFF of that leaf
        embed = state.params["embed"]
        require(len(embed.sharding.device_set) == 4, "embed not sharded")
        tuned = state._replace(params=dict(
            state.params, embed=embed.at[:64].add(1.0)))
        ctx.store(tuned, id=2, level=1, kind=CHK_DIFF)
        ctx.wait()
        ctx.shutdown()
        require([r.ckpt_id for r in reports] == [1, 2], "DIFF store lost")
        rep = reports[1]
        require(rep.kind == "DIFF" and not rep.promoted_full
                and rep.dirty_ratio < 0.5, f"DIFF store: {rep}")
        want = host_tree(tuned)
        del state, tuned

        template, shard_b = sharded_train_state(cfg, mesh_b)
        fresh = CheckpointContext(CheckpointConfig(dir=d, backend="fti"))
        got = fresh.load(template)
        fresh.shutdown()
        del template
        assert_bit_equal(host_tree(got), want, "4x1 restore vs DIFF store")
        assert_regions(got, want, shard_b, "4x1 DIFF restore")
        print(f"[smoke] four chips: DIFF of a sharded leaf (dirty ratio "
              f"{rep.dirty_ratio:.4f}) restored onto 4x1 bit-exact",
              flush=True)


# --------------------------------------------------------------------------- #


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 2x2 -> 4x1 phase (d)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[smoke] {len(devices)} x {dev.device_kind}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    Phase.listen()

    cfg = train_config()
    print(f"[smoke] {ARCH} at published widths (d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}); training depth cut 22 -> {cfg.n_layers} "
          f"layers, {cfg.param_count() / 1e6:.0f} M params", flush=True)
    work = os.path.join(HERE, ".chip_smoke")
    shutil.rmtree(work, ignore_errors=True)
    try:
        if args.four_chips:
            phase_four(work, cfg)
        else:
            phase_train(work, cfg)
            phase_diff(work, cfg)
            phase_serve(work)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
