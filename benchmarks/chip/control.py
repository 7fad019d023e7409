"""Readings that set a cell's limits, over many seeds, in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

For each seed it runs the cell's own set-up path up to the program's
readings of its first steps (no window, no saves), frees the program, and
compares with the float32 reference (``compare.gaps``):

- ``program``: the program itself, the lower readings;
- ``control``: the reference with every matrix product's operands and
  result rounded to float8_e4m3, in the backward pass too (the precision
  below the configuration's bfloat16);
- ``half_batch``: the reference over half of each batch's rows, the mean
  taken over those (a fault a training cell can have).

A step that returns its state unchanged reads 1 on ``grad_gap`` by
construction and needs no run.  Each reading is judged against the
cell's limits (``compare.judge``/``compare.passed``), as a run judges the
program's.  One JSON line per seed and reading (its numbers, whether it
came out correct, and the raw readings), then the largest program reading
and the smallest control and fault reading of each number.  The exit code
is 0 only where every program reading came out correct and every control
and fault reading not correct.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from types import SimpleNamespace

import run


def readings(workload: str, seed: int):
    """→ {kind: (numbers, raw readings)}, and the reference's raw readings."""
    import compare
    import reference
    import registry

    bench = registry.benchmark(run.ROOT)
    cell = registry.cell(bench, workload)
    w = registry.workload(workload)
    job = registry.driver(w["driver"]).Job(SimpleNamespace(
        name=workload, cell=cell, workload=w, config=registry.config(cell["config"]),
        seed=seed, work=run.OUT / workload, devices=None, trace=False))
    try:
        job.build()
        program = job.first_steps()
    finally:
        job.close()
    exact = job.reference()
    half = job.w["batch"] // 2
    raw = {"program": program, "control": job.reference(dot=reference.fp8_dot)}
    if half:
        raw["half_batch"] = job.reference(rows=half)
    return {kind: (compare.gaps(r, exact), r) for kind, r in raw.items()}, exact


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    run.prepare_environment()
    import jax

    import compare
    import registry
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    limits = registry.workload(args.workload)["limits"]
    lows, highs, wrong = {}, {}, []
    for seed in (int(s) for s in args.seeds.split(",")):
        found, exact = readings(args.workload, seed)
        print(json.dumps({"seed": seed, "reading": "reference", "raw": exact}), flush=True)
        for kind, (numbers, raw) in found.items():
            correct = compare.passed(compare.judge(numbers, limits))
            if correct != (kind == "program"):
                wrong.append((seed, kind))
            print(json.dumps({"seed": seed, "reading": kind, "correct": correct,
                              **numbers, "raw": raw}), flush=True)
            for k, v in numbers.items():
                if kind == "program":
                    lows[k] = max(lows.get(k, 0.0), v)
                else:
                    highs.setdefault(kind, {})[k] = min(highs.get(kind, {}).get(k, v), v)
    print(json.dumps({"lower": lows, "upper": highs, "misjudged": wrong}), flush=True)
    for seed, kind in wrong:
        print(f"[control] seed {seed}: the {kind} reading came out "
              f"{'not ' if kind == 'program' else ''}correct", file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
