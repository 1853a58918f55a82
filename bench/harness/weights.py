"""Weights from ``--seed``, made on the device in one jitted call.

A layout is a flat dict ``path -> (shape, dtype, kind, std)``: ``kind``
is ``normal`` (times ``std``) or ``zeros``.  Each leaf draws from
``fold_in(key, i)`` with ``i`` its rank in the sorted paths, so the
program and the reference get the same numbers from the same seed, and
the seed is an argument of the compiled call, not a constant in it.

Stacked learner replicas each draw their own values (``split`` of the
leaf's key): identical replicas are a fixed point of the learners'
mixing, so the exchange between them would show in nothing that the
first steps read.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def key_of(seed: int):
    return jax.random.PRNGKey(int(seed))


def _leaf(key, shape, dtype, kind, std):
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_fn(layout: dict, lead: int = 0):
    """``key -> {path: array}``; with ``lead`` > 0 every leaf is stacked
    ``lead`` times, one distinct draw per learner."""
    paths = sorted(layout)

    def make(key):
        out = {}
        for i, p in enumerate(paths):
            shape, dtype, kind, std = layout[p]
            k = jax.random.fold_in(key, i)
            if lead:
                out[p] = jax.vmap(lambda kk: _leaf(
                    kk, tuple(shape), dtype, kind, std))(
                        jax.random.split(k, lead))
            else:
                out[p] = _leaf(k, tuple(shape), dtype, kind, std)
        return out

    return make


def make_params(layout: dict, seed: int, lead: int = 0, shardings=None):
    fn = jax.jit(make_fn(layout, lead), out_shardings=shardings)
    return fn(key_of(seed))
