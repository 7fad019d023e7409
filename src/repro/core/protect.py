"""Protection registry: pytree ⇄ named arrays + clause-carrying selectors.

This is the layer that replaces the paper's compiler work (DESIGN.md §2):
Mercurium extracts base address / size / bounds from program symbols; here
pytree flattening extracts (path, dtype, shape, sharding) from the state the
user names. The user writes ``ctx.store(state, ...)`` — nothing is
hand-serialized.

Selectors are the analogue of *self-iterative data expressions* (§5.2):
``"params/groups/*/attn/**"`` expands over the tree exactly like
``{data[i], i=0;4}`` expands over an array.

A :class:`Protect` spec is a selector **plus the paper's per-data clauses**
(``kind(DIFF)``, compression codec, target format/precision, sharding-axis
metadata).  ``ctx.protect(Protect("params/**", kind=CHK_DIFF,
compress="int8"), Protect("step"))`` is the directive-level surface; the
resolved ``{path: Protect}`` map rides the StoreRequest/LoadRequest through
TCL → backend → pipeline, where the Pack-side tiers consume the clauses
(core/tiers.py).  Plain-string selectors remain accepted as a deprecated
shim and convert to clause-less specs.
"""
from __future__ import annotations

import re
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.tree_util import (
    tree_flatten_with_path,
    tree_unflatten,
    keystr,
)

CHK_FULL = "FULL"
CHK_DIFF = "DIFF"

#: codecs the Pack-side compression tier implements (core/tiers.py)
KNOWN_CODECS = ("int8",)
#: container formats the Pack-side format tier can emit
KNOWN_FORMATS = ("chk5",)
#: the gated-dependency message for ``Protect(format="hdf5")`` — raised at
#: *spec validation* time (constructing the spec), never deep in Pack, so
#: a misconfigured protect fails before any checkpoint machinery runs;
#: pinned verbatim by tests/test_protect_specs.py
HDF5_GATE_MSG = (
    "format='hdf5' needs h5py, which this environment does not ship; "
    "CHK5 keeps the same self-describing semantics (format='chk5')")
#: precision clause values → canonical dtype strings (core/formats.py
#: resolves them; bf16/fp8 need ml_dtypes, which jax ships)
PRECISIONS = {
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "f16": "<f2", "fp16": "<f2", "float16": "<f2",
    "f32": "<f4", "fp32": "<f4", "float32": "<f4",
}


@dataclass
class Protect:
    """One protected subtree: a selector plus per-subtree clauses.

    Clause fields (all optional — a clause-less spec is exactly the old
    flat selector):

    ``kind``       checkpoint kind for this subtree (``CHK_FULL`` /
                   ``CHK_DIFF``); ``None`` inherits the store's kind.
                   Mixed-kind stores (DIFF params + FULL optimizer in one
                   checkpoint) are expressed by giving subtrees different
                   kinds.
    ``compress``   Pack-side payload codec (``"int8"`` — per-block max-abs
                   quantization, dist/compression.py), roundtrip-verified
                   on load.
    ``format``     target container format tier (``"chk5"``).
    ``precision``  store-side dtype cast (``"bf16"`` …); restore casts back
                   to the template dtype.
    ``axis``       explicit axis metadata, e.g. ``{"batch": 1}`` — carried
                   to dist/sharding.py (cache layouts) and recorded as
                   dataset attrs.
    ``max_error``  relative-L2 bound for lossy codecs; a leaf whose
                   roundtrip error exceeds it is stored uncompressed
                   (``codec_fallback`` attr records why).
    """

    selector: str
    kind: Optional[str] = None
    compress: Optional[str] = None
    format: Optional[str] = None
    precision: Optional[str] = None
    axis: Optional[Dict[str, int]] = None
    max_error: Optional[float] = None
    _regex: Any = field(default=None, repr=False, compare=False)

    def __post_init__(self):
        if not isinstance(self.selector, str) or not self.selector:
            raise ValueError(f"Protect selector must be a non-empty string, "
                             f"got {self.selector!r}")
        if self.kind is not None and self.kind not in (CHK_FULL, CHK_DIFF):
            raise ValueError(f"Protect kind must be {CHK_FULL!r} or "
                             f"{CHK_DIFF!r}, got {self.kind!r}")
        if self.compress is not None and self.compress not in KNOWN_CODECS:
            raise ValueError(f"unknown compress codec {self.compress!r}; "
                             f"have {list(KNOWN_CODECS)}")
        if self.format is not None and self.format not in KNOWN_FORMATS:
            if self.format == "hdf5":
                raise ValueError(HDF5_GATE_MSG)
            raise ValueError(f"unknown format {self.format!r}; "
                             f"have {list(KNOWN_FORMATS)}")
        if self.precision is not None and self.precision not in PRECISIONS:
            raise ValueError(f"unknown precision {self.precision!r}; "
                             f"have {sorted(PRECISIONS)}")
        if self.axis is not None and not all(
                isinstance(k, str) and isinstance(v, int)
                for k, v in self.axis.items()):
            raise ValueError(f"Protect axis must map str → int dim, "
                             f"got {self.axis!r}")
        self._regex = _selector_regex(self.selector)

    # ------------------------------------------------------------------ #

    def matches(self, path: str) -> bool:
        return self._regex.match(path) is not None

    def clauses(self) -> Dict[str, Any]:
        """The non-empty clause fields — what the format tier records as
        dataset attributes (and ``chkls`` prints)."""
        out: Dict[str, Any] = {}
        for f in ("kind", "compress", "format", "precision", "axis",
                  "max_error"):
            v = getattr(self, f)
            if v is not None:
                out[f] = v
        return out


def _selector_regex(pat: str):
    """``**`` crosses slashes; ``*`` does not."""
    esc = re.escape(pat)
    esc = esc.replace(r"\*\*", ".*").replace(r"\*", "[^/]*")
    return re.compile("^" + esc + "$")


def normalize_protects(
    specs: Optional[Sequence[Union[str, Protect]]],
) -> Optional[List[Protect]]:
    """Directive-level shim: accept ``Protect`` specs and (deprecated)
    plain selector strings; strings become clause-less specs."""
    if not specs:
        return None
    out: List[Protect] = []
    legacy = []
    for s in specs:
        if isinstance(s, Protect):
            out.append(s)
        elif isinstance(s, str):
            legacy.append(s)
            out.append(Protect(s))
        else:
            raise TypeError(f"protect() takes Protect specs or selector "
                            f"strings, got {type(s).__name__}")
    if legacy:
        warnings.warn(
            f"flat selector strings {legacy} are deprecated; use "
            f"Protect(selector, ...) specs (clauses: kind/compress/"
            f"format/precision/axis)", DeprecationWarning, stacklevel=3)
    return out


def _key_str(k) -> str:
    """One pytree key → its path component, stripping only the keystr
    delimiters: ``['name']`` → ``name``, ``[0]`` → ``0``, ``.attr`` →
    ``attr``.  A dict key like ``".hidden"`` or ``"w.q"`` keeps its dots
    and quotes-in-content intact (the old ``strip("[]'\\".")`` ate them)."""
    s = keystr((k,))
    if s.startswith("[") and s.endswith("]"):
        s = s[1:-1]
        if len(s) >= 2 and s[0] == s[-1] and s[0] in ("'", '"'):
            s = s[1:-1]
    elif s.startswith("."):
        s = s[1:]
    return s


def _path_str(path) -> str:
    """KeyPath → canonical slash path: ('params','groups',0,'attn','wq') →
    "params/groups/0/attn/wq"."""
    return "/".join(_key_str(k) for k in path)


def flatten_named(tree: Any) -> Tuple[Dict[str, Any], Any]:
    """→ ({path: leaf}, treedef). Paths are stable across runs (dict order
    canonicalized by jax pytree registry)."""
    leaves, treedef = tree_flatten_with_path(tree)
    named = {}
    for path, leaf in leaves:
        p = _path_str(path)
        if p in named:
            raise ValueError(f"duplicate pytree path {p!r}")
        named[p] = leaf
    return named, treedef


def unflatten_named(treedef, named: Dict[str, Any], template: Any) -> Any:
    """Rebuild a tree shaped like ``template`` from named leaves (match by
    path; order-free — unlike the paper's order-critical load/store lists)."""
    t_leaves, t_def = tree_flatten_with_path(template)
    out = []
    for path, leaf in t_leaves:
        p = _path_str(path)
        if p not in named:
            raise KeyError(f"checkpoint missing leaf {p!r}")
        out.append(named[p])
    return tree_unflatten(t_def, out)


def resolve_specs(
    named: Dict[str, Any],
    protects: Optional[Sequence[Union[str, Protect]]],
) -> Dict[str, Optional[Protect]]:
    """Resolve clause specs over the flattened tree → ``{path: spec}``.

    ``None``/empty → every leaf, clause-less (``{path: None}``).  A leaf
    matched by several specs is selected **once**, governed by the *first*
    matching spec (specs are ordered, most-specific first by convention).
    A spec that matches no leaf is an error naming the offending selector —
    this is the "matched no leaves" path that ``ctx.load``/``ctx.store``
    surface to the user."""
    if not protects:
        return {path: None for path in named}
    specs = normalize_protects(protects)
    out: Dict[str, Optional[Protect]] = {}
    unmatched = []
    for spec in specs:
        hit = False
        for path in named:
            if spec.matches(path):
                hit = True
                out.setdefault(path, spec)     # first matching spec governs
        if not hit:
            unmatched.append(spec.selector)
    if unmatched:
        raise ValueError(
            f"Protect selectors {unmatched} matched no leaves "
            f"(all selectors: {[s.selector for s in specs]}; "
            f"protected paths: {sorted(named)[:8]}"
            f"{' …' if len(named) > 8 else ''})")
    # keep the tree's canonical leaf order, not match order
    return {path: out[path] for path in named if path in out}


def select(named: Dict[str, Any], patterns: Optional[List[str]]) -> Dict[str, Any]:
    """Glob-select protected leaves. ``None`` → everything. ``**`` crosses
    slashes; ``*`` does not.  (Compatibility wrapper over
    :func:`resolve_specs` — kept for callers that only need the leaves.)"""
    if not patterns:
        return dict(named)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        chosen = resolve_specs(named, list(patterns))
    return {path: named[path] for path in chosen}


def to_host(named: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Device→host snapshot of every protected leaf (one fused transfer)."""
    arrs = jax.device_get(list(named.values()))
    return {k: np.asarray(v) for k, v in zip(named.keys(), arrs)}


def leaf_bytes(leaves) -> int:
    """Bytes of ``leaves`` as arrays, read from their shapes (no transfer;
    a Python scalar counts as its NumPy form)."""
    return sum(int(v.nbytes) if hasattr(v, "nbytes") else np.asarray(v).nbytes
               for v in leaves)


def leaf_meta(named: Dict[str, Any]) -> Dict[str, Dict]:
    out = {}
    for k, v in named.items():
        out[k] = {"dtype": np.dtype(v.dtype).str, "shape": list(v.shape)}
    return out
