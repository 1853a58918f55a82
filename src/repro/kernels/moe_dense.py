"""Pallas TPU fused dense-MoE kernel (granite-style tiny experts).

Motivated by an attention-ablation measurement: after the combine
fusion, granite's residual memory term is the HBM round-trip of every
expert's hidden activations — (tokens, E, d_ff) at d_ff=512, E=40 is a
5× inflation of the active work and none of it needs to leave VMEM.

This kernel keeps one token tile (x: tile_t × d) resident, walks experts on
the inner grid axis streaming each expert's (wi, wg, wo) through VMEM once
per tile, and accumulates the router-weighted output in a VMEM scratch:

  grid = (n_token_tiles, E)
  y[tile] = sum_e router_w[tile, e] * swiglu(x wi_e, x wg_e) wo_e

HBM traffic per layer ≈ x + y + n_tiles × expert weights — the hidden
(tokens, E, d_ff) tensor never materializes.  Oracle:
``repro.kernels.ref.moe_dense_ref`` (== models/moe.py dense path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _moe_kernel(x_ref, w_ref, wi_ref, wg_ref, wo_ref, y_ref, acc_ref, *,
                act: str):
    """x_ref: (T, d); w_ref: (T, E_block=1) router weights for this expert;
    wi/wg: (d, f); wo: (f, d); y_ref: (T, d); acc: (T, d) f32 scratch."""
    e = pl.program_id(1)
    n_e = pl.num_programs(1)

    @pl.when(e == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    h = jax.lax.dot_general(x, wi_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if act == "swiglu":
        g = jax.lax.dot_general(x, wg_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    ye = jax.lax.dot_general(h.astype(x.dtype), wo_ref[...],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] += ye * w_ref[...].astype(jnp.float32)

    @pl.when(e == n_e - 1)
    def _emit():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def moe_dense(x, router_w, wi, wg, wo, *, act: str = "swiglu",
              tile_t: int = 1024, interpret: bool = None):
    """x: (T, d); router_w: (T, E) combine weights (0 for unselected);
    wi/wg: (E, d, f); wo: (E, f, d).  Returns (T, d)."""
    T, d = x.shape
    E, _, f = wi.shape
    tile_t = min(tile_t, T)
    assert T % tile_t == 0, (T, tile_t)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(_moe_kernel, act=act)
    return pl.pallas_call(
        kernel,
        grid=(T // tile_t, E),
        in_specs=[
            pl.BlockSpec((tile_t, d), lambda t, e: (t, 0)),
            pl.BlockSpec((tile_t, 1), lambda t, e: (t, e)),
            pl.BlockSpec((None, d, f), lambda t, e: (e, 0, 0)),
            pl.BlockSpec((None, d, f), lambda t, e: (e, 0, 0)),
            pl.BlockSpec((None, f, d), lambda t, e: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_t, d), lambda t, e: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((tile_t, d), jnp.float32)],
        interpret=interpret,
    )(x, router_w, wi, wg, wo)
