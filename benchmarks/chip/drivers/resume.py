"""Traffic ``resume``: a dropped training job restarting from its checkpoint.

Set-up makes the cell's ``TrainState`` from the seed (step 0), stores it
once through the directive surface (``kind`` FULL at the workload's
``level``), frees it from the device, and runs one resume as warm-up.  The
checkpoint then sits in the host's page cache, as it does after a process
crash on the same node.

Each resume is what a restarted worker does: ``launch/train.py``'s
``worker()`` for one step past the stored one, in this process — a
throwaway init, ``ctx.load``, the step traced again (a compilation-cache
hit), one step, and the result on the device (``block_until_ready``).  The
window repeats resumes until ``--seconds`` are over; ``resume_s`` is the
mean wall time of the resumes in it.
"""
from __future__ import annotations

import sys
import time
from typing import Any, Dict, List

import jax
import numpy as np

import compare
import reference
import registry
import trainjob


class Job:
    def __init__(self, run) -> None:
        self.run = run
        self.w, self.c = run.workload, run.config
        self.family = registry.reference(run.config)
        self.seed32 = run.seed & 0xFFFFFFFF
        self.attempted = 0
        self.failed = 0
        self.durations: List[float] = []
        self.program: Dict[str, Any] = {}
        self.params0 = None

    def _template(self):
        from repro.data.synthetic import init_data_state
        from repro.train.state import init_train_state
        params = self.family.init_params(self.keys["params"], self.c)
        return init_train_state(params, jax.numpy.copy(self.keys["rng"]),
                                init_data_state(self.seed32))

    def _context(self):
        from repro.core.context import CheckpointConfig, CheckpointContext
        return CheckpointContext(CheckpointConfig(
            dir=str(self.run.work / "ckpt"), backend=self.w["store"]["backend"]))

    def setup(self) -> None:
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.train import parse_args
        from repro.models.zoo import build_model

        enable_compile_cache()
        self.cfg = self.family.arch_config(self.c)
        self.keys = trainjob.keys(self.run.seed)
        state = self._template()
        trainjob.check_layout(build_model(self.cfg), state.params, self.c)
        ctx = self._context()
        try:
            ctx.store(state, id=1, level=self.w["store"]["level"], kind="FULL")
            ctx.wait()
        finally:
            ctx.shutdown()
            trainjob.free(state)
        w = self.w
        self.args = parse_args([
            "--ckpt-dir", str(self.run.work / "ckpt"), "--steps", "1",
            "--batch", str(w["batch"]), "--seq", str(w["seq"]), "--seed", "0",
            "--ckpt-every", "2", "--backend", w["store"]["backend"]])
        self.params0 = self.family.init_params(self.keys["params"], self.c)
        self._resume()
        self.attempted = self.failed = 0
        self.durations = []

    def _resume(self) -> None:
        """One restart; its readings replace the previous resume's."""
        from repro.launch.train import worker

        self.attempted += 1
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("resume"):
                summary = worker(self.args, cfg=self.cfg)
                state = jax.block_until_ready(summary["state"])
        except Exception as e:  # noqa: BLE001 - a failed resume is counted
            self.failed += 1
            print(f"[bench] resume failed: {e!r}", flush=True)
            return
        self.durations.append(time.perf_counter() - t0)
        if not summary["restarted"] or int(state.step) != 1:
            self.failed += 1
        self.program = {
            "loss": [summary["loss"]],
            "grad": trainjob.first_gradient_norms(state.opt.mu, self.w["optimizer"]["b1"]),
            "change": trainjob.change_norms(state.params, self.params0),
            "paths": trainjob.leaf_paths(state.params),
        }
        trainjob.free(state)

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            while time.perf_counter() - t0 < seconds:
                self._resume()
        print(f"[bench] window: resumes of {[round(d, 3) for d in self.durations]} s",
              file=sys.stderr, flush=True)

    def end_to_end(self) -> Dict[str, float]:
        d = self.durations
        return {"resume_s": sum(d) / len(d) if d else float("nan")}

    def observations(self) -> Dict[str, Any]:
        return {"resume_s": list(self.durations)}

    def _batch(self, index: int):
        """The rows the worker's data cursor (seed, position ``index``) gives."""
        key = jax.random.fold_in(jax.random.PRNGKey(np.uint32(self.seed32)), index)
        toks = jax.random.randint(key, (self.w["batch"], self.w["seq"] + 1), 0,
                                  self.c["vocab_size"], jax.numpy.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def check(self) -> Dict[str, float]:
        """The stored checkpoint read back through a fresh context, bit for
        bit against the state made again from the seed; then the resumed
        step against the reference's first step."""
        trainjob.free(self.params0)
        self.params0 = None
        want = self._template()
        ctx = self._context()
        try:
            got = ctx.load(want)
            same = [bool(np.array_equal(np.asarray(a), np.asarray(b)))
                    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
            restarted = ctx.restarted
        finally:
            ctx.shutdown()
        trainjob.free(got, want)
        numbers = {"restore_mismatch_leaves": float(same.count(False) + (not restarted))}
        if not self.program:
            return dict(numbers, loss_gap=float("nan"))
        numbers.update(compare.gaps(self.program, self.reference()))
        return numbers

    def reference(self, dot=reference.exact_dot, rows=None) -> Dict[str, Any]:
        def batch(i):
            b = self._batch(i)
            return b if rows is None else {k: v[:rows] for k, v in b.items()}
        return trainjob.reference_readings(
            self.family, self.c, self.w["optimizer"], self.keys["params"], batch, 1, "all",
            dot=dot)

    def build(self) -> None:
        """For the control: set-up up to the first resume's readings."""
        self.setup()

    def first_steps(self) -> Dict[str, Any]:
        return self.program

    def close(self) -> None:
        trainjob.free(self.params0)
        self.params0 = None
