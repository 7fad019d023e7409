"""Mesh axis conventions and the sharding-hint layer.

Axis conventions (every mesh in this repo uses these names):

- ``MODEL`` (``"model"``) — tensor parallelism. The only axis parameter
  feature dims ever shard over.
- ``DATA`` (``"data"``) — data parallelism (batch dim, ZeRO/FSDP shards).
- ``POD`` (``"pod"``) — an outer data-parallel axis on multi-pod meshes.
  Anything that shards on ``DATA`` folds ``pod`` in: requesting ``DATA``
  resolves to *every non-model axis* of the active mesh, so model code
  never cares whether it runs on ``(data, model)`` or ``(pod, data,
  model)``.

The hint layer is deliberately no-op-safe: model code calls
``shard_hint`` unconditionally; without an active mesh (CPU tests,
single-device serving) or with hints disabled (``constraint_hints(False)``
— the dp-only ablation) the input is returned unchanged, so hints never
constrain programs that did not opt in via ``use_mesh``.

Every resolution is divisibility-aware: an axis is kept only when the dim
it shards divides evenly by the axis size (GSPMD would otherwise pad or
fail); dims that do not divide degrade to replicated, and a spec whose
every requested axis dissolved resolves to ``None`` (caller falls back).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

DATA = "data"
MODEL = "model"
POD = "pod"

_state = threading.local()


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes — every mesh in this repo is
    built here.  The hint layer constrains with ``with_sharding_constraint``
    and lets GSPMD propagate the rest, which ``Explicit`` axes (the
    ``jax.make_mesh`` default since jax 0.7) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def _st():
    if not hasattr(_state, "mesh"):
        _state.mesh = None
        _state.hints = True
    return _state


def active_mesh() -> Optional[Mesh]:
    """The mesh entered via ``use_mesh``, or None (hints no-op)."""
    return _st().mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Make ``mesh`` ambient for the hint layer (trace-time: wrap the
    ``jit``/``lower`` call, not the execution)."""
    st = _st()
    prev = st.mesh
    st.mesh = mesh
    try:
        yield mesh
    finally:
        st.mesh = prev


@contextlib.contextmanager
def constraint_hints(enabled: bool):
    """Toggle the hint layer (``False`` → every hint is identity). The
    dp-only dry-run disables hints so TP constraints never fight a
    replicated-parameter layout."""
    st = _st()
    prev = st.hints
    st.hints = bool(enabled)
    try:
        yield
    finally:
        st.hints = prev


def hints_enabled() -> bool:
    return _st().hints


# --------------------------------------------------------------------------- #
# axis resolution
# --------------------------------------------------------------------------- #


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Every non-model axis, mesh order — ``("data",)`` or
    ``("pod", "data")``. This is the pod→data folding rule."""
    return tuple(a for a in mesh.axis_names if a != MODEL)


def _axis_size(mesh: Mesh, axis: Any) -> int:
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _expand(mesh: Mesh, axis: Any) -> Any:
    """Expand an axis request against the active mesh: ``DATA`` folds all
    data axes; names absent from the mesh dissolve to None."""
    if axis is None:
        return None
    if axis == DATA:
        dax = data_axes(mesh)
        if not dax:
            return None
        return dax[0] if len(dax) == 1 else dax
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in mesh.axis_names)
        return kept if kept else None
    return axis if axis in mesh.axis_names else None


def resolve_spec(mesh: Mesh, axes: Sequence[Any],
                 shape: Sequence[int]) -> Optional[P]:
    """Divisibility-aware spec resolution.

    Per dim: keep the requested axis iff the dim divides by the (folded)
    axis size, else degrade that dim to replicated. Returns ``None`` when
    every requested axis dissolved — the caller's signal to fall back to
    its next rule rather than emit an all-replicated constraint.
    """
    dims = []
    kept = 0
    for i, dim in enumerate(shape):
        axis = _expand(mesh, axes[i] if i < len(axes) else None)
        if axis is not None and dim % _axis_size(mesh, axis) == 0:
            dims.append(axis)
            kept += 1
        else:
            dims.append(None)
    if kept == 0:
        return None
    return P(*dims)


# --------------------------------------------------------------------------- #
# hints (no-op-safe: identity without an active mesh)
# --------------------------------------------------------------------------- #


def shard_hint(x: Any, *axes: Any) -> Any:
    """``with_sharding_constraint`` against the active mesh, or ``x``
    unchanged when there is no mesh, hints are disabled, or no requested
    axis survives divisibility."""
    mesh = active_mesh()
    if mesh is None or not _st().hints:
        return x
    spec = resolve_spec(mesh, axes, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_decode_kv(x: Any, model_dim: Optional[int] = 2) -> Any:
    """Decode-path KV/latent cache constraint, layout chosen by shape:

    - batch divides the data axes → batch-sharded decode (dim 0 on DATA);
    - else sequence-sharded long-context decode (dim 1 — the cache-seq
      dim — on DATA): scores/softmax/PV reduce over the sharded dim and
      GSPMD emits partial-softmax all-reduces instead of a KV gather;
    - ``model_dim`` (the repeated-heads dim; None for MLA latents) shards
      on MODEL when divisible.
    """
    mesh = active_mesh()
    if mesh is None or not _st().hints:
        return x
    dax = _expand(mesh, DATA)
    dims: list = [None] * x.ndim
    if dax is not None:
        dsize = _axis_size(mesh, dax)
        if x.shape[0] % dsize == 0:
            dims[0] = dax
        elif x.ndim >= 2 and x.shape[1] % dsize == 0:
            dims[1] = dax
    if (model_dim is not None and model_dim < x.ndim
            and MODEL in mesh.axis_names
            and x.shape[model_dim] % mesh.shape[MODEL] == 0):
        dims[model_dim] = MODEL
    if all(d is None for d in dims):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*dims)))
