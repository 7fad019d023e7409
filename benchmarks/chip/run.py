"""On-chip benchmark of checkpointing a training job: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process on the machine that holds the chip.  It exits non-zero, and
prints no result, when JAX finds no TPU or fewer chips than the cell asks
for, or when the program (``src/repro``) is not beside it.

A run: set-up (weights and optimizer state from the seed, compilation of
the cell's own shapes, warm-up steps and saves) → a window of ``--seconds``
driven by the cell's driver → the program's state freed → the comparison
with the plain reference that decides ``correct``.  With ``--trace 1`` the
window runs under the JAX profiler and the result carries the cell's
per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks`` (each compared number with its limit,
also the last lines of standard error).  Working files (checkpoints,
traces, JAX's compilation cache) go under ``benchmarks/chip/out/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
OUT = HERE / "out"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_environment() -> None:
    """The program on the path, and JAX's persistent compilation cache at
    one fixed directory inside the checkout (the path is part of the key)."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(OUT / "jax_cache")


def require_accelerator(chips: int):
    """The devices of the run: ``chips`` TPU chips, or exit without a result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX finds {len(devices)} "
            f"{devices[0].platform} device(s)")
        raise SystemExit(3)
    return devices[:chips]


class CompileCounter:
    """Counts XLA compilations: backend compiles that were not a hit of
    the persistent compilation cache."""

    def __init__(self) -> None:
        import jax
        self.compiles = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def count(self) -> int:
        return self.compiles - self.hits

    def _on_duration(self, event, duration, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def execute(args: argparse.Namespace) -> dict:
    """Set-up, window, check; → the result object (printed by ``main``)."""
    import jax

    import compare
    import registry

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = registry.benchmark(ROOT)
    cell = registry.cell(bench, args.workload)
    workload = registry.workload(args.workload)
    config = registry.config(cell["config"])
    devices = require_accelerator(int(cell["chips"]))
    counter = CompileCounter()

    work = OUT / args.workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    run = SimpleNamespace(name=args.workload, cell=cell, workload=workload,
                          config=config, seed=args.seed, work=work,
                          devices=devices, trace=bool(args.trace))
    job = registry.driver(workload["driver"]).Job(run)
    try:
        job.setup()
        setup_s = time.perf_counter() - T_START
        log(f"set-up {setup_s:.3f} s, {counter.count} compilations "
            f"({counter.seconds:.3f} s)")
        before = counter.count
        trace_dir = work / "trace"
        if args.trace:
            from repro.telemetry import trace as ttrace
            ttrace.enable()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), create_perfetto_trace=True,
                                     profiler_options=options)
        try:
            job.window(args.seconds)
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        log(f"compilations in the window: {counter.count - before}")
        peak = memory_peak(devices)
        obs = job.observations()
        numbers = job.check()
    finally:
        job.close()
    checks = compare.judge(numbers, workload["limits"])
    checks["failed"] = {"value": job.failed, "limit": 0}

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": compare.passed(checks), "attempted": job.attempted,
              "failed": job.failed, "metrics": {}, "device": device}
    if args.trace:
        import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir, len(devices))
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        obs.update(trace=reduced, peaks=registry.peaks(kind), spans=ttrace.tracer().events())
        for m in registry.per_layer(bench, args.workload):
            value = registry.metric_reader(m["name"]).read(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(job.end_to_end(), setup_s=setup_s)
        for m in registry.end_to_end(bench, args.workload):
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["checks"] = checks
    shutil.rmtree(work / "ckpt", ignore_errors=True)
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"the program is not beside the benchmark ({ROOT / 'src' / 'repro'})")
        return 2
    prepare_environment()
    result = execute(args)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
