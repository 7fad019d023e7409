"""Fig. 12 analogue: wall-time overhead of OpenCHK vs native backends.

Methodology reproduced from §6.1: first run with a fault injected at 90 %
progress, then restart to completion; time the whole process. Ratio
OpenCHK/native should be ≈1 (paper: within noise, <2 % worst case).
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
import time
from typing import Dict

from benchmarks.apps import heat2d_fti, heat2d_openchk, heat2d_scr, heat2d_veloc
from repro.ft.failures import FaultInjector, SimulatedFault

STEPS = 200
N = 768             # 2.25 MB grid → checkpoint I/O is non-trivial
EVERY = 20          # 10 checkpoints per run, like the paper's 1/minute × 10


def timed_run_with_fault(mod, ckpt_dir, backend=None) -> float:
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    # warm the jit cache so compile time isn't charged to the first variant
    from benchmarks.apps.heat2d_common import heat_step, init_grid
    heat_step(init_grid(N)).block_until_ready()
    t0 = time.time()
    inj = FaultInjector(total_steps=STEPS, at_progress=0.9)
    try:
        mod.run(n=N, steps=STEPS, ckpt_every=EVERY, ckpt_dir=ckpt_dir,
                injector=inj, backend=backend)
    except SimulatedFault:
        # a real abort kills the CP thread with the process; the in-process
        # simulation must drain it so the restart doesn't race an orphan
        from repro.core.async_engine import drain_all
        drain_all()
    out = mod.run(n=N, steps=STEPS, ckpt_every=EVERY, ckpt_dir=ckpt_dir,
                  backend=backend)
    assert out["restarted"], "restart did not engage"
    dt = time.time() - t0
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    return dt


def compressed_store(repeats: int = 3) -> Dict[str, float]:
    """Compressed-store datapoint: payload ratio and store-path overhead
    of an int8-compressed FULL store (Pack-side Int8CompressTier,
    ``Protect(compress="int8")``) vs an uncompressed FULL store of the
    same state.  Synchronous fti so the Pack tail is inside the timing.

    The byte ratio is deterministic (~0.25 + scale/index overhead); the
    time ratio pays the quantize+roundtrip-verify cost against a 4x
    smaller write — CI gates both (check_overhead_regression.py)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.context import CheckpointConfig, CheckpointContext, Protect

    n = 1 << 22                      # 16 MiB of f32 payload
    rng = np.random.default_rng(0)
    state = {"params": {"w": jnp.asarray(rng.normal(size=n)
                                         .astype(np.float32))}}
    best: Dict[str, tuple] = {}
    variants = {"full": [Protect("params/**")],
                "int8": [Protect("params/**", compress="int8")]}
    for tag, protects in variants.items():
        times, nbytes = [], 0
        for r in range(repeats):
            d = f"/tmp/bo-compress-{tag}"
            shutil.rmtree(d, ignore_errors=True)
            ctx = CheckpointContext(CheckpointConfig(
                dir=d, backend="fti", dedicated_thread=False))
            ctx.protect(*protects)
            t0 = time.time()
            rep = ctx.store(state, id=1, level=1)
            times.append(time.time() - t0)
            nbytes = rep.bytes_payload
            ctx.shutdown()
            shutil.rmtree(d, ignore_errors=True)
        best[tag] = (min(times), nbytes)
    return {
        "compress_full_store_s": best["full"][0],
        "compress_int8_store_s": best["int8"][0],
        "compress_ratio_int8": best["int8"][1] / best["full"][1],
        "compress_store_overhead_int8": best["int8"][0] / best["full"][0],
    }


def telemetry_overhead(repeats: int = 3) -> Dict[str, float]:
    """Telemetry-plane overhead datapoint: wall time of a traced L4 store
    (span recorder + metrics registry live, so every instrumented stage —
    Plan/Pack/Place/Commit spans, chunk-upload spans, metric increments —
    records for real) vs the same store with telemetry disabled (the
    no-op fast path).  Synchronous fti, interleaved repeats; the ratio is
    the min over per-round (on/off) pairs — adjacent runs share whatever
    the box was doing, so pairing cancels drift that a min-of-mins ratio
    eats whole, while a systematic cost still shows in every round.
    ``telemetry_overhead_ratio`` is hard-gated at 1.05 in
    check_overhead_regression.py — the plane's contract is that
    observability never costs real store time."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.context import CheckpointConfig, CheckpointContext
    from repro.telemetry import trace as ttrace

    n = 1 << 22                      # 16 MiB of f32 payload
    rng = np.random.default_rng(0)
    state = {"params": {"w": jnp.asarray(rng.normal(size=n)
                                         .astype(np.float32))}}

    def one_store(tag: str) -> float:
        d = f"/tmp/bo-telemetry-{tag}"
        shutil.rmtree(d, ignore_errors=True)
        ctx = CheckpointContext(CheckpointConfig(
            dir=d, backend="fti", dedicated_thread=False))
        t0 = time.time()
        ctx.store(state, id=1, level=4)
        dt = time.time() - t0
        ctx.shutdown()
        shutil.rmtree(d, ignore_errors=True)
        return dt

    def arm(tag: str) -> None:
        if tag == "on":
            ttrace.tracer().reset()  # keep the event list from compounding
            ttrace.enable()
        else:
            ttrace.disable()

    variants = ("off", "on")
    times: Dict[str, list] = {t: [] for t in variants}
    try:
        for tag in variants:                      # warmup: jit + page cache
            arm(tag)
            one_store(tag)
        for _ in range(max(repeats, 5)):          # interleave: shared drift
            for tag in variants:                  # hits both variants alike
                arm(tag)
                times[tag].append(one_store(tag))
    finally:
        ttrace.disable()
        ttrace.tracer().reset()
    ratios = [on / off for off, on in zip(times["off"], times["on"])]
    return {
        "telemetry_off_store_s": min(times["off"]),
        "telemetry_on_store_s": min(times["on"]),
        "telemetry_overhead_ratio": min(ratios),
    }


def objstore_store(repeats: int = 3) -> Dict[str, float]:
    """Object-store L4 datapoint: wall time of a chunked+cataloged store
    (``objstore_store_s``), the store-path goodput
    (``objstore_goodput_bps`` = payload bytes / first-store wall time —
    the zero-stall fused Pack → upload path keeps this near local write
    bandwidth because Place never re-reads staged files) and the dedup
    ratio — a second store after a small param delta must upload <30% of
    the first's bytes (unchanged content-addressed chunks upload
    nothing; both gated in check_overhead_regression.py).  Synchronous
    fti so the Place uploads + Commit catalog publish are inside the
    timing."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.context import CheckpointConfig, CheckpointContext

    n = 1 << 23                      # 32 MiB of f32 payload → 32 chunks
    rng = np.random.default_rng(0)
    base = rng.normal(size=n).astype(np.float32)
    times, ratios, goodputs = [], [], []
    for r in range(repeats):
        d = "/tmp/bo-objstore"
        shutil.rmtree(d, ignore_errors=True)
        ctx = CheckpointContext(CheckpointConfig(
            dir=d, backend="fti", dedicated_thread=False))
        tier = ctx.tcl.backend.engine.objstore_tier()
        t0 = time.time()
        rep = ctx.store({"params": {"w": jnp.asarray(base)}}, id=1, level=4)
        dt = time.time() - t0
        times.append(dt)
        goodputs.append(rep.bytes_payload / max(dt, 1e-9))
        up1 = tier.uploader.stats["bytes_uploaded"]
        delta = base.copy()
        delta[:4096] += 1.0          # a small param delta
        ctx.store({"params": {"w": jnp.asarray(delta)}}, id=2, level=4)
        ratios.append((tier.uploader.stats["bytes_uploaded"] - up1)
                      / max(up1, 1))
        ctx.shutdown()
        shutil.rmtree(d, ignore_errors=True)
    return {"objstore_store_s": min(times),
            "objstore_goodput_bps": max(goodputs),
            "objstore_dedup_ratio": min(ratios)}


def objstore_shift_dedup() -> Dict[str, float]:
    """Boundary-shift dedup datapoint (deterministic, byte-level — no
    timing): 16 MiB of random bytes, then the same payload with 1 KiB
    inserted at the 25 % mark, streamed through the CDC chunk uploader.
    A fixed-size chunker re-uploads every chunk after the insertion
    point (offsets shift); content-defined cuts re-synchronize within
    ~one average chunk, so the re-uploaded delta must be well under the
    fixed-size cost.  ``objstore_shift_dedup_vs_fixed`` = CDC delta
    bytes / fixed-size delta bytes, gated hard at 0.30 in
    check_overhead_regression.py."""
    import hashlib
    import numpy as np
    from repro.objstore.cdc import CDCParams
    from repro.objstore.chunks import ChunkUploader, DEFAULT_CHUNK_BYTES
    from repro.objstore.client import MemoryObjectStore

    rng = np.random.default_rng(7)
    v1 = rng.integers(0, 256, 16 << 20, dtype=np.uint8).tobytes()
    insert = rng.integers(0, 256, 1024, dtype=np.uint8).tobytes()
    at = len(v1) // 4
    v2 = v1[:at] + insert + v1[at:]

    up = ChunkUploader(MemoryObjectStore(), cdc=CDCParams())
    for tag, payload in (("v1", v1), ("v2", v2)):
        before = up.stats["bytes_uploaded"]
        s = up.open_stream(tag)
        s.write(payload)
        s.finish()
        s.pending().result()
        if tag == "v2":
            cdc_delta = up.stats["bytes_uploaded"] - before
    up.close()

    def fixed_digests(buf):
        return [(hashlib.sha256(buf[o:o + DEFAULT_CHUNK_BYTES]).hexdigest(),
                 len(buf[o:o + DEFAULT_CHUNK_BYTES]))
                for o in range(0, len(buf), DEFAULT_CHUNK_BYTES)]
    seen = {h for h, _ in fixed_digests(v1)}
    fixed_delta = sum(n for h, n in fixed_digests(v2) if h not in seen)
    return {"objstore_shift_dedup_vs_fixed": cdc_delta / max(fixed_delta, 1)}


def serve_swap_delta() -> Dict[str, float]:
    """Checkpoint-as-deployment datapoint (deterministic, byte-level — no
    timing): publish a FULL checkpoint, publish a fine-tuned successor
    (small param delta), then pull the successor into a replica whose
    chunk cache already holds the first — exactly what a rolling hot-swap
    (``repro.serve.deploy``) does between consecutive deploys.
    ``serve_swap_delta_ratio`` = fetched / (fetched + cached) bytes of
    the second pull; content addressing makes it ~the dedup ratio of the
    underlying store, hard-gated at 0.30 in check_overhead_regression.py
    alongside the catalog-level prediction (``CatalogView.diff``)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.context import CheckpointConfig, CheckpointContext
    from repro.objstore.client import make_object_store
    from repro.objstore.inspect import CatalogView
    from repro.serve.deploy import EntryPuller

    n = 1 << 23                      # 32 MiB of f32 payload
    rng = np.random.default_rng(0)
    base = rng.normal(size=n).astype(np.float32)
    d = "/tmp/bo-serve-swap"
    shutil.rmtree(d, ignore_errors=True)
    ctx = CheckpointContext(CheckpointConfig(
        dir=d, backend="fti", dedicated_thread=False))
    ctx.store({"params": {"w": jnp.asarray(base)}}, id=1, level=4)
    tuned = base.copy()
    tuned[:4096] += 1.0              # a small fine-tune delta
    ctx.store({"params": {"w": jnp.asarray(tuned)}}, id=2, level=4)
    ctx.shutdown()

    store = make_object_store("file:" + os.path.join(d, "objstore"))
    view = CatalogView.from_store(store)
    puller = EntryPuller(store, os.path.join(d, "replica-cache"))
    puller.pull(view.entry(1))       # the replica deployed v1 earlier
    got = puller.pull(view.entry(2))
    fetched, cached = got["bytes_fetched"], got["bytes_cached"]
    predicted = CatalogView.diff(view.entry(1), view.entry(2)).ratio
    shutil.rmtree(d, ignore_errors=True)
    return {"serve_swap_delta_ratio": fetched / max(fetched + cached, 1),
            "serve_swap_delta_predicted": predicted}


def cadence_datapoints() -> Dict[str, float]:
    """Daly cadence datapoint (deterministic — no timing): drive the
    CadenceController with the reference platform's inputs (store cost
    observations, failures at exact MTBF spacing — comd-ft's 1000-node
    point: delta 48.64 s, MTBF 31557.6 s) and surface its L4 schedule
    against the closed-form optimum.

    - ``cadence_interval_vs_optimum`` — controller interval / closed-form
      Daly optimum; hard-gated to [0.9, 1.1] in
      check_overhead_regression.py (the estimator sees 200 failures at
      exact spacing, so drifting past 10% means the estimator or the
      interval math broke, not noise).
    - ``checkpoint_efficiency`` — best achievable progress fraction at
      the controller's schedule; floor-gated against the committed
      baseline.
    - ``progress_rate`` — progress fraction at the (clamped) interval
      actually scheduled."""
    from repro.chaos.cadence import (
        REFERENCE, CadenceConfig, CadenceController, daly_interval)

    p = REFERENCE.platform(1000)
    ctl = CadenceController(CadenceConfig(max_interval_s=1e9))
    for _ in range(8):
        ctl.note_store(4, p.delta_s)           # measured store cost
    ctl.note_step(0.0)
    for i in range(1, 201):                    # failures at exact spacing
        ctl.note_failure(i * p.mtbf_s)
    dp = ctl.datapoints(4)
    ref = daly_interval(p.delta_s, p.mtbf_s)
    return {
        "progress_rate": dp["progress_rate"],
        "checkpoint_efficiency": dp["checkpoint_efficiency"],
        "cadence_interval_vs_optimum": dp["cadence_interval_s"] / ref,
    }


def chaos_mttr(repeats: int = 3) -> Dict[str, float]:
    """Compound-fault recovery datapoint: run the node-loss-mid-store
    chaos scenario (real store → torn mid-flight store → node kill →
    partner restore, fti backend) and surface best-of-N MTTR plus the
    zero-loss invariant.

    - ``chaos_mttr_s`` — wall time from node death to a verified
      bit-exact partner restore; best-of-N to shed scheduler noise, and
      gated in check_overhead_regression.py with an absolute floor
      (sub-second restores never fail) plus a wide regression multiple.
    - ``chaos_data_loss_bytes`` — must be exactly 0 (hard gate: the
      scenario contract is that faults may cost time, never data)."""
    import tempfile

    from repro.chaos.scenarios import run_scenario

    best = None
    loss = 0.0
    for _ in range(max(repeats, 1)):
        with tempfile.TemporaryDirectory(prefix="bo-chaos-") as d:
            r = run_scenario("node-loss-mid-store", "fti", d)
            if not r.ok:
                raise RuntimeError(f"chaos scenario failed: {r.detail}")
            loss += float(r.data_loss_bytes)
            m = r.mttr_s if r.mttr_s is not None else r.recovery_s
            best = m if best is None else min(best, m)
    return {"chaos_mttr_s": best, "chaos_data_loss_bytes": loss}


_SHARDED_SCRIPT = textwrap.dedent("""
    import os, sys, json, time, shutil
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.context import CheckpointConfig, CheckpointContext
    from repro.dist.context import make_mesh

    repeats = max(int(sys.argv[1]), 5)
    mesh = make_mesh((4, 4), ("data", "model"))
    n = 1 << 12                       # 4096x4096 f32 = 64 MiB of payload
    host = np.arange(n * n, dtype=np.float32).reshape(n, n)
    sh = NamedSharding(mesh, P("data", "model"))

    # veloc (sync): no digest bookkeeping, so the timing isolates the
    # snapshot+pack+commit path the datapoint is about; a fresh device
    # array per repeat keeps jax's cached host copy from flattering the
    # gather variant
    def one_store(tag, sharded):
        w = jax.device_put(host, sh)
        jax.block_until_ready(w)
        d = f"/tmp/bo-shard-{tag}"
        shutil.rmtree(d, ignore_errors=True)
        ctx = CheckpointContext(CheckpointConfig(
            dir=d, backend="veloc", dedicated_thread=False,
            sharded_snapshot=sharded))
        os.sync()       # settle writeback: fsync inside the store must not
        t0 = time.time()    # pay for the previous variant's dirty pages
        ctx.store({"w": w}, id=1, level=1)
        dt = time.time() - t0
        ctx.shutdown()
        shutil.rmtree(d, ignore_errors=True)
        return dt

    variants = (("sharded", True), ("gathered", False))
    for tag, sharded in variants:
        one_store(tag, sharded)                   # warmup: jit + page cache
    times = {tag: [] for tag, _ in variants}
    for r in range(repeats):                      # interleave: shared drift
        for tag, sharded in variants:             # hits both variants alike
            times[tag].append(one_store(tag, sharded))
    out = {f"{tag}_store_s": min(ts) for tag, ts in times.items()}
    print("RESULT " + json.dumps(out))
""")


def sharded_store(repeats: int = 3) -> Dict[str, float]:
    """Sharded-store datapoint on the forced-16-device mesh: one store of
    a 64 MiB leaf sharded 4x4, snapshotting per-shard (shard-local Plan +
    parallel shard-file writes) vs gathering the full array to host.  The
    sharded path must not be slower — it moves the same bytes but skips
    the global host buffer and writes chunks in parallel.  Runs in a
    subprocess (device count locks at jax init)."""
    # forced host devices: the child must never reach for an accelerator
    # (this parent may already hold it)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT, str(repeats)],
                       capture_output=True, text=True, timeout=900, env=env)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert r.returncode == 0 and lines, (
        f"sharded-store bench subprocess failed (rc={r.returncode}):\n"
        f"{r.stdout[-2000:]}\n{r.stderr[-3000:]}")
    got = json.loads(lines[0][len("RESULT "):])
    return {
        "sharded_store_s": got["sharded_store_s"],
        "gathered_store_s": got["gathered_store_s"],
        "sharded_store_speedup":
            got["gathered_store_s"] / max(got["sharded_store_s"], 1e-9),
    }


def run(repeats: int = 3) -> Dict[str, float]:
    natives = {"fti": heat2d_fti, "scr": heat2d_scr, "veloc": heat2d_veloc}
    out: Dict[str, float] = {}
    for backend, native_mod in natives.items():
        # interleave native/openchk repeats (like the sharded-store bench)
        # so shared machine drift hits both variants alike — sequential
        # blocks bias the ratio by whatever the host was doing during the
        # second block
        t_native, t_openchk = [], []
        for _ in range(repeats):
            t_native.append(timed_run_with_fault(
                native_mod, f"/tmp/bo-native-{backend}"))
            t_openchk.append(timed_run_with_fault(
                heat2d_openchk, f"/tmp/bo-openchk-{backend}", backend=backend))
        out[f"native_{backend}_s"] = min(t_native)
        out[f"openchk_{backend}_s"] = min(t_openchk)
        out[f"overhead_ratio_{backend}"] = min(t_openchk) / min(t_native)
    out.update(compressed_store(repeats=repeats))
    out.update(telemetry_overhead(repeats=repeats))
    out.update(sharded_store(repeats=repeats))
    out.update(objstore_store(repeats=repeats))
    out.update(objstore_shift_dedup())
    out.update(serve_swap_delta())
    out.update(cadence_datapoints())
    out.update(chaos_mttr(repeats=repeats))
    return out


def rows(repeats: int = 2):
    r = run(repeats)
    return [("overhead/" + k, v * 1e6 if k.endswith("_s") else 0.0, v)
            for k, v in sorted(r.items())]


if __name__ == "__main__":
    for name, us, v in rows():
        print(f"{name},{us},{v}")
