"""Traffic ``train_save``: a training job that saves every K steps.

The job is what ``train/loop.py`` runs: the jitted step between calls of
the directive surface, ``CheckpointContext.store`` every ``store.every``
steps, asynchronously on the CP thread.  The workload file says what
trains (``"all"``: the whole model through ``train/step.make_train_step``;
a list of parts of the stage's last layer, e.g. ``["attn", "ln1"]``: those
and the final norm, everything else frozen and without optimizer state,
built from ``compute_loss`` and ``adamw_update`` on the tree as the
configuration's reference family cuts it) and how it saves
(``kind``, ``level``, ``every``, optional ``protect`` selectors).

Set-up makes weights and optimizer state on the device from the seed,
compiles the step, runs the first three steps (their readings are what the
reference is compared with), then warms up the save path: for FULL its
device programs only (no checkpoint written), for DIFF two saves, the FULL
base and one delta at the dirty count the window will see, then K steps.
The window is whole save cycles, each a save and then K steps, until
``--seconds`` are over, and then the wait for the last save's tail: a
cycle that begins with a full CP queue, as a job's do, waits for the
previous tail in its save.  ``tokens_per_s`` is every token of every step
over the whole window, that last wait included.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time
import weakref
from typing import Any, Dict, List

import jax
import numpy as np

import compare
import digests
import reference
import registry
import trainjob

FIRST_STEPS = 3


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def finetune_forward(params, batch, cfg, remat=False):
    """The model with the layers before the last run forward only:
    ``params`` holds ``rest`` (the model without its last layer, as the
    reference family's ``split_last`` gives it, the trained final norm in
    place) and ``last`` (the last layer, its trained parts in place)."""
    import jax.numpy as jnp
    from repro.models.layers import cast_floating
    from repro.models.transformer import lm_backbone, lm_logits

    cdt = jnp.dtype(cfg.compute_dtype)
    rest = params["rest"]
    h = rest["embed"][batch["tokens"]].astype(cdt)
    if cfg.n_layers > 1:
        h, _ = lm_backbone({"groups": cast_floating(rest["groups"], cdt)}, h, cfg,
                           remat=remat)
    last = cast_floating(jax.tree.map(lambda x: x[None], params["last"]), cdt)
    h, aux = lm_backbone({"groups": [last]}, h, cfg, remat=remat)
    head = {k: rest[k] for k in ("ln_f", "embed", "lm_head") if k in rest}
    return lm_logits(cast_floating(head, cdt), h, cfg), aux


def _read_leaves(family, params, parts) -> List[int]:
    """Indices of the leaves of ``params`` that the family's ``trained_part``
    takes the trained parts from: each leaf stands in as a broadcast view
    of its own index."""
    leaves, treedef = jax.tree.flatten(params)
    probe = treedef.unflatten([np.broadcast_to(np.int32(i), x.shape)
                               for i, x in enumerate(leaves)])
    return sorted({int(x.flat[0]) for x in jax.tree.leaves(family.trained_part(probe, parts))})


def make_finetune_step(model, opt_cfg, family, params, parts):
    """step(state, batch) → (state, loss): AdamW on the parts ``parts`` of the
    last layer and on the final norm (``family.trained_part``); ``state.opt``
    covers only those.  The leaves of ``params``' tree that hold no trained
    part stay the same arrays."""
    from repro.train.optimizer import adamw_update
    from repro.train.step import compute_loss

    ft_model = dataclasses.replace(
        model, forward=functools.partial(finetune_forward, cfg=model.cfg))
    written = _read_leaves(family, params, parts)

    def step(state, batch):
        rest, last = family.split_last(state.params)
        trained = family.trained_part(state.params, parts)

        def loss_of(tr):
            p = {"rest": dict(rest, ln_f=tr["ln_f"]), "last": dict(last, **tr["last"])}
            loss, _ = compute_loss(ft_model, p, batch, remat=True)
            return loss

        loss, grads = jax.value_and_grad(loss_of)(trained)
        new_tr, new_opt, _ = adamw_update(opt_cfg, grads, state.opt, trained)
        joined = family.join_last(dict(rest, ln_f=new_tr["ln_f"]),
                                  dict(last, **new_tr["last"]))
        if jax.tree.structure(joined) != jax.tree.structure(state.params):
            raise ValueError("the reference family's join_last does not give back "
                             "the tree that split_last took")
        new = jax.tree.leaves(joined)
        return state.step + 1, {i: new[i] for i in written}, new_opt, loss

    jitted = jax.jit(step)

    def run(state, batch):
        count, new, opt, loss = jitted(state, batch)
        leaves, treedef = jax.tree.flatten(state.params)
        params = treedef.unflatten([new.get(i, x) for i, x in enumerate(leaves)])
        return state._replace(step=count, params=params, opt=opt), loss

    return run


class Job:
    def __init__(self, run) -> None:
        self.run = run
        self.w, self.c = run.workload, run.config
        self.family = registry.reference(run.config)
        self.attempted = 0
        self.failed = 0
        self.block_s: List[float] = []
        self.reports: List[Any] = []
        self.saved: List[Any] = []          # (checkpoint id, fingerprint)
        self.hashed_bytes = 0
        self._seen: Dict[str, Any] = {}
        self.newest_hashed: List[str] = []

    # ------------------------------------------------------------------ #

    def setup(self) -> None:
        from repro.core.context import CheckpointConfig, CheckpointContext, Protect

        self.build()
        s = self.w["store"]
        self.ctx = CheckpointContext(CheckpointConfig(
            dir=str(self.run.work / "ckpt"), backend=s["backend"]))
        self.ctx.observe_store_reports(self.reports.append)
        if s.get("protect"):
            self.ctx.protect(*[Protect(p) for p in s["protect"]])
        self.program = self.first_steps()
        if s["kind"] == "DIFF":
            self._save()
            self._steps(s["every"])
            self._save()
            self.ctx.wait()
            self._steps(s["every"])
        else:
            self._warm_full_save()
        if self.failed:
            raise RuntimeError("a warm-up save failed")
        print(f"[bench] set-up saves wrote {sum(r.bytes_payload for r in self.reports)} "
              "bytes", file=sys.stderr, flush=True)
        self.block_s, self.saved = [], []
        self.reports.clear()
        self.attempted = self.failed = self.hashed_bytes = 0

    def _warm_full_save(self) -> None:
        """Compile what a FULL save runs on the device (its digest pass,
        one blockhash program per leaf shape, and the fingerprint) without
        writing a checkpoint: each run of the cell writes only the
        window's saves."""
        from repro.kernels import ops
        leaves = jax.tree.leaves(self.state)
        jax.block_until_ready([ops.blockhash(x, self.ctx.cfg.block_bytes) for x in leaves])
        jax.block_until_ready(trainjob.fingerprint(self.state))

    def build(self) -> None:
        """Weights, optimizer state and the jitted step, from the seed."""
        from repro.data.synthetic import init_data_state
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models.zoo import build_model
        from repro.train.optimizer import adamw_init
        from repro.train.state import TrainState, init_train_state
        from repro.train.step import make_train_step

        enable_compile_cache()
        w, c, seed = self.w, self.c, self.run.seed
        self.cfg = self.family.arch_config(c)
        self.model = build_model(self.cfg)
        self.opt_cfg = trainjob.adamw_config(w["optimizer"])
        self.keys = trainjob.keys(seed)
        params = self.family.init_params(self.keys["params"], c)
        trainjob.check_layout(self.model, params, c)
        if w["trained"] == "all":
            self.state = init_train_state(params, jax.numpy.copy(self.keys["rng"]),
                                          init_data_state(seed & 0xFFFFFFFF))
            jitted = jax.jit(make_train_step(self.model, self.opt_cfg, remat=True))

            def step(state, batch):
                state, metrics = jitted(state, batch)
                return state, metrics["loss"]
            self.step = step
        else:
            self.state = TrainState(
                step=jax.numpy.zeros((), jax.numpy.int32), params=params,
                opt=adamw_init(self._trained(params)),
                rng=jax.numpy.copy(self.keys["rng"]),
                data_state=init_data_state(seed & 0xFFFFFFFF))
            self.step = make_finetune_step(self.model, self.opt_cfg, self.family, params,
                                           w["trained"])
        self.index = 0

    def _batch(self, index: int):
        w = self.w
        return trainjob.make_batch(self.keys["data"], index, w["batch"], w["seq"],
                                   self.c["vocab_size"])

    def _trained(self, params):
        return self.family.trained_part(params, self.w["trained"])

    def first_steps(self) -> Dict[str, Any]:
        """Steps 1..3 through the window's own call and feed; the program's
        readings of them."""
        out: Dict[str, Any] = {"loss": []}
        for i in range(FIRST_STEPS):
            self.state, loss = self.step(self.state, self._batch(self.index))
            self.index += 1
            out["loss"].append(float(loss))
            if i == 0:
                out["grad"] = trainjob.first_gradient_norms(self.state.opt.mu,
                                                            self.opt_cfg.b1)
        start = self._trained(self.family.init_params(self.keys["params"], self.c))
        now = self._trained(self.state.params)
        out["paths"] = trainjob.leaf_paths(now)
        out["change"] = trainjob.change_norms(now, start)
        trainjob.free(start)
        return out

    def _steps(self, n: int) -> None:
        for _ in range(n):
            with _annotate("step"):
                self.state, _ = self.step(self.state, self._batch(self.index))
            self.index += 1

    def _count_hashed(self) -> None:
        """Bytes the digest pass of this save reads: every leaf that is not
        the same array as at the previous save (``newest_hashed``: their
        paths as the checkpoint names them)."""
        from repro.core.protect import flatten_named
        named, _ = flatten_named(self.state)
        self.newest_hashed = []
        for path, leaf in named.items():
            ref = self._seen.get(path)
            if ref is None or ref() is not leaf:
                self.hashed_bytes += leaf.size * leaf.dtype.itemsize
                self.newest_hashed.append(path)
            self._seen[path] = weakref.ref(leaf)

    def _save(self) -> None:
        s = self.w["store"]
        fingerprint = trainjob.fingerprint(self.state)
        self._count_hashed()
        self.attempted += 1
        # the steps before the save finish first, so the time below is the
        # store's own (Plan waits for them anyway)
        jax.block_until_ready(self.state)
        t0 = time.perf_counter()
        try:
            with _annotate("ctx.store"):
                self.ctx.store(self.state, id=self.index, level=s["level"], kind=s["kind"])
        except Exception as e:  # noqa: BLE001 - a failed save is counted
            self.failed += 1
            print(f"[bench] save {self.index} failed: {e!r}", flush=True)
            return
        self.block_s.append(time.perf_counter() - t0)
        self.saved.append((self.index, fingerprint))

    # ------------------------------------------------------------------ #

    def window(self, seconds: float) -> None:
        every = self.w["store"]["every"]
        steps = 0
        t0 = time.perf_counter()
        with _annotate("window"):
            while steps == 0 or time.perf_counter() - t0 < seconds:
                self._save()
                self._steps(every)
                steps += every
            with _annotate("ctx.wait"):
                try:
                    self.ctx.wait()
                except Exception as e:  # noqa: BLE001 - surfaced as failed saves
                    print(f"[bench] a save of the window failed: {e!r}", flush=True)
            jax.block_until_ready(self.state)
        self.window_s = time.perf_counter() - t0
        self.window_steps = steps
        committed = {r.ckpt_id for r in self.reports}
        self.failed += sum(1 for i, _ in self.saved if i not in committed)
        dirty = [r.dirty_ratio for r in self.reports if r.dirty_ratio is not None]
        print(f"[bench] window: {steps} steps in {self.window_s:.3f} s, "
              f"{len(self.saved)} saves wrote {sum(r.bytes_payload for r in self.reports)} "
              f"bytes, dirty ratio {dirty[-1] if dirty else None}", file=sys.stderr, flush=True)

    def end_to_end(self) -> Dict[str, float]:
        return {"tokens_per_s": self.window_steps * trainjob.tokens_per_step(self.w)
                / self.window_s}

    def observations(self) -> Dict[str, Any]:
        return {
            "window_s": self.window_s,
            "steps": self.window_steps,
            "flops_per_step": self.family.step_flops(self.c, self.w),
            "store_block_s": list(self.block_s),
            "save_tail_s": [r.seconds for r in self.reports],
            "hashed_bytes": self.hashed_bytes,
        }

    # ------------------------------------------------------------------ #

    def check(self) -> Dict[str, float]:
        """Restore the newest checkpoint of the window through a fresh
        context and compare it with what was stored; free the program's
        state; then the reference's first steps against the program's."""
        from repro.core.context import CheckpointConfig, CheckpointContext, Protect

        numbers: Dict[str, float] = {}
        if self.saved:
            newest, stored = self.saved[-1]
            s = self.w["store"]
            fresh = CheckpointContext(CheckpointConfig(
                dir=str(self.run.work / "ckpt"), backend=s["backend"]))
            if s.get("protect"):
                fresh.protect(*[Protect(p) for p in s["protect"]])
            try:
                restored = fresh.load(self.state)
                got = np.asarray(trainjob.fingerprint(restored))
                ok_step = int(restored.step) == newest
                numbers["digest_mismatch_blocks"] = self._digest_mismatch(restored)
                trainjob.free(restored)
            finally:
                fresh.shutdown()
            numbers["restore_mismatch_leaves"] = float(
                np.sum(np.any(got != np.asarray(stored), axis=1)) + (not ok_step))
        else:
            numbers["restore_mismatch_leaves"] = float("nan")
            numbers["digest_mismatch_blocks"] = float("nan")
        self.close()
        numbers.update(compare.gaps(self.program, self.reference()))
        return numbers

    def _digest_mismatch(self, restored) -> float:
        """Blocks whose digest, as the newest save recorded it for the
        largest leaf that save hashed, differs from ``digests.block_digests``
        of that leaf as restored (NaN: no digest recorded for any of them)."""
        from repro.core.protect import flatten_named
        table = self.ctx.tcl.backend.pipeline.diff._digests
        named, _ = flatten_named(restored)
        recorded = [p for p in self.newest_hashed if p in table]
        if not recorded:
            return float("nan")
        path = max(recorded, key=lambda p: named[p].size * named[p].dtype.itemsize)
        want = digests.block_digests(np.asarray(named[path]), self.ctx.cfg.block_bytes)
        got = np.asarray(table[path])
        print(f"[bench] digests of {path}: {want.shape[0]} blocks", file=sys.stderr,
              flush=True)
        if got.shape != want.shape:
            return float(want.shape[0])
        return float(np.sum(np.any(got != want, axis=1)))

    def reference(self, dot=reference.exact_dot, rows=None) -> Dict[str, Any]:
        """The reference's first steps on the same weights and rows (``rows``:
        only the first that many rows of each batch)."""
        def batch(i):
            b = self._batch(i)
            return b if rows is None else {k: v[:rows] for k, v in b.items()}
        return trainjob.reference_readings(
            self.family, self.c, self.w["optimizer"], self.keys["params"], batch,
            FIRST_STEPS, self.w["trained"], dot=dot)

    def close(self) -> None:
        ctx, self.ctx = getattr(self, "ctx", None), None
        try:
            if ctx is not None:
                ctx.shutdown()
        finally:
            trainjob.free(getattr(self, "state", None))
            self.state = None
