"""What both training drivers share: the optimizer's configuration as the
program takes it, inputs and keys from the seed, and the first steps of a
job read on the program's side and on the reference family's (the
family's own pieces, the model's configuration as the program takes it
among them, are in ``references/<family>.py``).

The program's readings (taken by a driver from its own first steps) and
the reference's (``reference_readings``) have one shape: the loss of each
step, the norm of each trained leaf's first gradient as the optimizer got
it (after clipping), and the norm of each trained leaf's change over the
steps.  ``compare.gaps`` turns two of them into the numbers that decide
``correct``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import reference

Readings = Dict[str, Any]


# --------------------------------------------------------------------------- #
# seed, inputs, weights
# --------------------------------------------------------------------------- #


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def keys(seed: int) -> Dict[str, Any]:
    k_params, k_data, k_rng = jax.random.split(seed_key(seed), 3)
    return {"params": k_params, "data": k_data, "rng": k_rng}


@functools.partial(jax.jit, static_argnames=("batch", "seq", "vocab"))
def make_batch(key, index, batch: int, seq: int, vocab: int):
    """The rows of step ``index``: uniform token ids, labels shifted by one."""
    toks = jax.random.randint(jax.random.fold_in(key, index), (batch, seq + 1),
                              0, vocab, jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def tokens_per_step(w: Dict[str, Any]) -> int:
    return int(w["batch"]) * int(w["seq"])


# --------------------------------------------------------------------------- #
# the program's view of the optimizer, and the layout check
# --------------------------------------------------------------------------- #


def adamw_config(o: Dict[str, Any]):
    from repro.train.optimizer import AdamWConfig
    return AdamWConfig(**{k: o[k] for k in AdamWConfig._fields})


def check_layout(model, params, c: Dict[str, Any]) -> None:
    """The weights that configuration ``c``'s reference family made must be
    the tree the program takes."""
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)), model.param_struct())
    got = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), params)
    if want != got:
        raise SystemExit("the program's parameter layout differs from the benchmark's "
                         f"weights; update references/{c['reference']}.py")


# --------------------------------------------------------------------------- #
# per-leaf numbers
# --------------------------------------------------------------------------- #


def leaf_paths(tree) -> List[str]:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_norms(tree) -> List[float]:
    return [float(v) for v in np.asarray(_norms(tree))]


@jax.jit
def _diff_norms(a, b):
    return _norms(jax.tree.map(lambda x, y: x.astype(jnp.float32) - y, a, b))


def change_norms(new, old) -> List[float]:
    return [float(v) for v in np.asarray(_diff_norms(new, old))]


def _as_u32(x):
    x = x.reshape(-1)
    size = jnp.dtype(x.dtype).itemsize
    if size == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if size == 8:
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
    wide = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * size}"))
    return wide.astype(jnp.uint32)


@jax.jit
def fingerprint(tree):
    """Per leaf three wrapping sums of its 32-bit words (plain, weighted by
    odd position weights, and mixed): any flipped bit changes a row.  Stays
    on the device until read."""
    rows = []
    for x in jax.tree.leaves(tree):
        u = _as_u32(x)
        w = jnp.arange(u.shape[0], dtype=jnp.uint32) * np.uint32(2) + np.uint32(1)
        rows.append(jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                               jnp.sum(u * w, dtype=jnp.uint32),
                               jnp.sum(u ^ (w * np.uint32(0x9E3779B1)),
                                       dtype=jnp.uint32)]))
    return jnp.stack(rows)


# --------------------------------------------------------------------------- #
# the reference's first steps
# --------------------------------------------------------------------------- #


def reference_readings(family, c: Dict[str, Any], o: Dict[str, Any], params_key,
                       batch_fn: Callable[[int], Dict[str, Any]], n_steps: int,
                       trained, dot=reference.exact_dot) -> Readings:
    """Run ``n_steps`` AdamW steps of configuration ``c``'s reference family
    (``registry.reference``) from the seed's weights on ``batch_fn(i)``
    (i = 0 .. n_steps-1); ``trained`` as the family's ``trained_part``
    takes it.  Float32 at the highest precision unless ``dot`` says
    otherwise."""
    params = family.init_params(params_key, c)
    tr0 = family.trained_part(params, trained)
    if trained == "all":
        def loss_fn(tr, frozen, b):
            return family.loss(tr, b["tokens"], b["labels"], c, dot)
    else:
        def loss_fn(tr, frozen, b):
            return family.loss(frozen, b["tokens"], b["labels"], c, dot, trained=tr)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    adam = jax.jit(functools.partial(reference.adamw, o), static_argnames=("count",))
    tr = tr0
    mu = jax.tree.map(jnp.zeros_like, tr)
    nu = jax.tree.map(jnp.zeros_like, tr)
    out: Readings = {"loss": [], "paths": leaf_paths(tr)}
    for i in range(n_steps):
        loss, grads = grad_fn(tr, params, batch_fn(i))
        tr, mu, nu, clipped = adam(grads, mu, nu, tr, count=i + 1)
        out["loss"].append(float(loss))
        if i == 0:
            out["grad"] = leaf_norms(clipped)
        del grads, clipped
    out["change"] = change_norms(tr, tr0)
    return out


def first_gradient_norms(mu, b1: float) -> List[float]:
    """The first gradient as the optimizer got it, from its state after one
    step: mu_1 = (1 - b1) g_1."""
    return [v / (1.0 - b1) for v in leaf_norms(mu)]


def free(*trees: Optional[Any]) -> None:
    for t in trees:
        for x in jax.tree.leaves(t):
            if isinstance(x, jax.Array) and not x.is_deleted():
                x.delete()
