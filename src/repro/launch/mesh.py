"""Production mesh construction (multi-pod dry-run deliverable).

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state.
"""
from __future__ import annotations

from repro.dist.context import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (examples/tests)."""
    return make_mesh((1, 1), ("data", "model"))
