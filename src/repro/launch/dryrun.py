import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"     # placeholder host devices, never a chip

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh and extract memory/cost/collective analyses for §Dry-run
and §Roofline of EXPERIMENTS.md.

The lines above MUST precede any jax-importing import: jax locks the
device count at first init, and the dry-run needs 512 placeholder host
devices to build the 16×16 (single-pod) and 2×16×16 (multi-pod) meshes.
They are CPU devices on every host, so the per-cell children it spawns
never reach for an accelerator.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out reports/dryrun]
  (--all spawns one subprocess per cell for isolation/progress persistence)

Perf knobs (the §Perf hillclimb drives these):
  --zero1          shard optimizer moments over the data axes (ZeRO-1)
  --fsdp           additionally shard parameters over data (weight gather)
  --param-dtype    bfloat16|float32 parameter storage
  --moe-dispatch   einsum|scatter
  --no-remat       disable activation checkpointing
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
from typing import Any, Dict, Optional

import jax
import numpy as np


def _build_shardings(mesh, model, state_struct, zero1: bool, fsdp: bool,
                     dp_only: bool = False):
    """TrainState shardings: params per rules (+FSDP), moments (+ZeRO-1).

    ``dp_only``: treat the model axis as extra data parallelism — params
    replicated (or FSDP-sharded) over ALL axes, no tensor parallelism. The
    right strategy for small dense models where TP psums dominate (§Perf B).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.context import data_axes
    from repro.dist.sharding import param_shardings

    if dp_only:
        pshard = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), state_struct.params)
    else:
        pshard = param_shardings(mesh, state_struct.params)
    dax = data_axes(mesh) + (("model",) if dp_only else ())
    dp = 1
    for a in dax:
        dp *= mesh.shape[a]
    daxis = dax if len(dax) > 1 else dax[0]

    def augment(sharding, leaf):
        """Add the data axes to the first unsharded divisible dim."""
        spec = list(sharding.spec) + [None] * (len(leaf.shape) - len(sharding.spec))
        for i, (s, dim) in enumerate(zip(spec, leaf.shape)):
            if s is None and dim % dp == 0 and dim >= dp:
                spec[i] = daxis
                return NamedSharding(mesh, P(*spec))
        return sharding

    mshard = pshard
    if zero1:
        mshard = jax.tree.map(augment, pshard, state_struct.params)
    if fsdp:
        pshard = jax.tree.map(augment, pshard, state_struct.params)

    scalar = NamedSharding(mesh, P())
    from repro.train.optimizer import AdamWState
    from repro.train.state import TrainState
    return TrainState(
        step=scalar,
        params=pshard,
        opt=AdamWState(count=scalar, mu=mshard, nu=mshard),
        rng=scalar,
        data_state=jax.tree.map(lambda _: scalar, state_struct.data_state),
    )


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             zero1: bool = False, fsdp: bool = False,
             dp_only: bool = False,
             param_dtype: Optional[str] = None,
             moe_dispatch: Optional[str] = None,
             remat: bool = True,
             q_block: Optional[int] = None,
             out_path: Optional[str] = None,
             verbose: bool = True) -> Dict[str, Any]:
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import SHAPE_BY_NAME, get_arch
    from repro.data.synthetic import data_state_struct
    from repro.dist.context import constraint_hints, use_mesh
    from repro.dist.sharding import batch_sharding, cache_shardings
    from repro.launch.mesh import make_production_mesh
    from repro.models.zoo import batch_struct, build_model
    from repro.roofline.analyze import build_report
    from repro.train.optimizer import AdamWConfig
    from repro.train.state import train_state_struct
    from repro.train.step import make_train_step

    cfg = get_arch(arch)
    if param_dtype:
        cfg = dataclasses.replace(cfg, param_dtype=param_dtype)
    if moe_dispatch and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=moe_dispatch))
    shape = SHAPE_BY_NAME[shape_name]
    if shape not in cfg.shapes():
        out = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "skipped",
               "reason": "full-attention arch: long-context decode N/A "
                         "(DESIGN.md §5)"}
        if out_path:
            os.makedirs(os.path.dirname(out_path), exist_ok=True)
            with open(out_path, "w") as f:
                json.dump(out, f, indent=1)
        return out

    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    def _batch_shard(ndim: int):
        if not dp_only:
            return batch_sharding(mesh, ndim)
        # greedy: extend the batch axes only while the batch stays divisible
        axes: list = []
        n = 1
        for a in ("pod", "data", "model"):
            if a in mesh.axis_names and \
                    shape.global_batch % (n * mesh.shape[a]) == 0:
                axes.append(a)
                n *= mesh.shape[a]
        if not axes:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(tuple(axes), *([None] * (ndim - 1))))

    import contextlib
    hint_ctx = constraint_hints(not dp_only) if dp_only else \
        contextlib.nullcontext()
    with use_mesh(mesh), hint_ctx:
        if shape.kind == "train":
            state_struct = train_state_struct(model.param_struct(),
                                              data_state_struct())
            bstruct = batch_struct(cfg, shape.global_batch, shape.seq_len)
            in_shardings = (
                _build_shardings(mesh, model, state_struct, zero1, fsdp,
                                 dp_only=dp_only),
                jax.tree.map(lambda s: _batch_shard(len(s.shape)), bstruct),
            )
            step = make_train_step(model, AdamWConfig(), remat=remat)
            lowered = jax.jit(
                step, in_shardings=in_shardings,
                out_shardings=(in_shardings[0],
                               NamedSharding(mesh, P())),
                donate_argnums=(0,),   # state buffers reused in place
            ).lower(state_struct, bstruct)
        elif shape.kind == "prefill":
            pstruct = model.param_struct()
            pshard = _build_shardings(
                mesh, model, _FakeState(pstruct), zero1=False,
                fsdp=fsdp).params
            bstruct = batch_struct(cfg, shape.global_batch, shape.seq_len)
            bstruct.pop("labels")
            bshard = jax.tree.map(
                lambda s: batch_sharding(mesh, len(s.shape)), bstruct)

            def prefill_step(params, batch):
                logits, _ = model.forward(params, batch, remat=False)
                return jax.numpy.argmax(logits[:, -1], axis=-1)

            lowered = jax.jit(
                prefill_step, in_shardings=(pshard, bshard),
            ).lower(pstruct, bstruct)
        else:  # decode
            from repro.serve.engine import make_serve_step
            pstruct = model.param_struct()
            pshard = _build_shardings(
                mesh, model, _FakeState(pstruct), zero1=False,
                fsdp=fsdp).params
            cstruct = model.cache_struct(shape.global_batch, shape.seq_len)
            seq_sharded = shape.global_batch == 1
            cshard = cache_shardings(mesh, cstruct, shape.global_batch,
                                     seq_axis_sharded=seq_sharded,
                                     protects=model.cache_protects())
            tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jax.numpy.int32)
            tshard = batch_sharding(
                mesh, 2, batch_divisible=shape.global_batch > 1)
            pos_s = jax.ShapeDtypeStruct((), jax.numpy.int32)
            serve_step = make_serve_step(model)
            lowered = jax.jit(
                serve_step,
                in_shardings=(pshard, tshard, cshard,
                              NamedSharding(mesh, P())),
                out_shardings=(tshard, cshard),
                donate_argnums=(2,),   # KV caches updated in place
            ).lower(pstruct, tok, cstruct, jax.numpy.int32(0))

        compile_t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - compile_t0

    mem = compiled.memory_analysis()
    print(mem)                                  # proves it fits
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):         # older jax: dict per device
        cost = cost[0] if cost else {}
    print({k: cost.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()

    peak = None
    mem_detail = {}
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem_detail[k] = getattr(mem, k, None)
        peak = (mem_detail.get("temp_size_in_bytes") or 0) + \
               (mem_detail.get("argument_size_in_bytes") or 0)

    rep = build_report(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        cost=cost, hlo_text=hlo,
        model_flops_total=cfg.model_flops(shape),
        peak_memory=peak,
    )
    hlo_diag = rep.to_dict()

    # primary roofline terms: analytic model (HLO cost_analysis counts scan
    # bodies once — see roofline/analytic.py; HLO numbers kept as diagnostics)
    from repro.dist.context import data_axes
    from repro.roofline.analytic import analytic_report
    dp = 1
    for a in data_axes(mesh):
        dp *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    if dp_only:
        # effective DP is capped by the global batch (surplus devices
        # replicate — multi-pod dp-only wants global_batch ≥ chips)
        dp, tp = min(dp * tp, shape.global_batch), 1
    ana = analytic_report(cfg, shape, dp=dp, tp=tp, remat=remat,
                          zero1=zero1, fsdp=fsdp)

    out = dict(ana)
    out.update(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        status="ok", compile_seconds=compile_s,
        total_seconds=time.time() - t0, memory=mem_detail,
        peak_memory_per_device=peak,
        hlo_diagnostics={
            "flops_body_once": hlo_diag["flops_per_device"],
            "bytes_body_once": hlo_diag["bytes_per_device"],
            "wire_body_once": hlo_diag["wire_bytes_per_device"],
            "collectives": hlo_diag["collectives"],
        },
        knobs={"zero1": zero1, "fsdp": fsdp, "dp_only": dp_only,
               "param_dtype": param_dtype or cfg.param_dtype,
               "moe_dispatch": moe_dispatch, "remat": remat})
    if verbose:
        print(json.dumps({k: out[k] for k in (
            "arch", "shape", "mesh", "bottleneck", "t_compute", "t_memory",
            "t_collective", "roofline_fraction", "useful_flops_ratio")},
            indent=1))
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1, default=float)
    return out


class _FakeState:
    """Adapter so _build_shardings can shard bare params."""

    def __init__(self, params):
        self.params = params
        from repro.data.synthetic import data_state_struct
        from repro.train.optimizer import AdamWState
        import jax.numpy as jnp
        s = jax.ShapeDtypeStruct((), jnp.int32)
        self.opt = AdamWState(s, params, params)
        self.step = s
        self.rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        self.data_state = data_state_struct()


def _all_cells(args) -> int:
    from repro.configs import ALL_ARCHS, ALL_SHAPES
    failures = []
    for arch in ALL_ARCHS:
        for shape in [s.name for s in ALL_SHAPES]:
            for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
                mesh_name = "multi" if mp else "single"
                out = os.path.join(
                    args.out, f"{arch}__{shape}__{mesh_name}.json")
                if os.path.exists(out) and not args.force:
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out-file", out]
                if mp:
                    cmd.append("--multi-pod")
                for flag in ("zero1", "fsdp"):
                    if getattr(args, flag):
                        cmd.append(f"--{flag}")
                if args.param_dtype:
                    cmd += ["--param-dtype", args.param_dtype]
                print(f"=== {arch} × {shape} × {mesh_name}", flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True)
                if r.returncode != 0:
                    failures.append((arch, shape, mesh_name))
                    print(r.stdout[-2000:])
                    print(r.stderr[-3000:])
    print(f"done; {len(failures)} failures: {failures}")
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--dp-only", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--param-dtype")
    ap.add_argument("--moe-dispatch")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--out-file")
    args = ap.parse_args()

    if args.all:
        return _all_cells(args)
    assert args.arch and args.shape, "--arch/--shape or --all"
    out = run_cell(args.arch, args.shape, args.multi_pod,
                   zero1=args.zero1, fsdp=args.fsdp, dp_only=args.dp_only,
                   param_dtype=args.param_dtype,
                   moe_dispatch=args.moe_dispatch,
                   remat=not args.no_remat,
                   out_path=args.out_file)
    return 0 if out.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
