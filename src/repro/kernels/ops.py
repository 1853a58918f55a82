"""Jit'd public wrappers around the Pallas kernels.

Model code selects these with ``kernel_impl='pallas'``; on non-TPU
backends the kernels execute in interpret mode (Python evaluation of the
kernel body — correct, slow), which is how CI validates them against the
``ref.py`` oracles.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention
from repro.kernels.lstm_cell import blstm_sequence as _blstm_sequence
from repro.kernels.lstm_cell import lstm_sequence as _lstm_sequence
from repro.kernels.moe_dense import moe_dense as _moe_dense
from repro.kernels.ssd_scan import ssd as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "q_offset"))
def attention(q, k, v, *, causal: bool = True, window: int = 0,
              block_q: int = 512, block_k: int = 512, q_offset: int = 0):
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           q_offset=q_offset)


@functools.partial(jax.jit, static_argnames=("reverse", "block_b",
                                             "vmem_budget", "stash_dtype",
                                             "seq_chunk"))
def lstm_sequence(wx, wh, b, x, lengths=None, *, reverse: bool = False,
                  block_b: int = None, vmem_budget: int = None,
                  stash_dtype: str = None, seq_chunk: int = 0):
    return _lstm_sequence(wx, wh, b, x, lengths, reverse=reverse,
                          block_b=block_b, vmem_budget=vmem_budget,
                          stash_dtype=stash_dtype, seq_chunk=seq_chunk)


@functools.partial(jax.jit, static_argnames=("block_b", "vmem_budget",
                                             "stash_dtype", "seq_chunk"))
def blstm_sequence(wx_fwd, wh_fwd, b_fwd, wx_bwd, wh_bwd, b_bwd, x,
                   lengths=None, *, block_b: int = None,
                   vmem_budget: int = None, stash_dtype: str = None,
                   seq_chunk: int = 0):
    return _blstm_sequence(wx_fwd, wh_fwd, b_fwd, wx_bwd, wh_bwd, b_bwd, x,
                           lengths, block_b=block_b,
                           vmem_budget=vmem_budget,
                           stash_dtype=stash_dtype, seq_chunk=seq_chunk)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A, Bm, Cm, *, chunk: int = 256):
    return _ssd(x, dt, A, Bm, Cm, chunk=chunk)


@functools.partial(jax.jit, static_argnames=("act", "tile_t"))
def moe_dense(x, router_w, wi, wg, wo, *, act: str = "swiglu",
              tile_t: int = 1024):
    return _moe_dense(x, router_w, wi, wg, wo, act=act, tile_t=tile_t)
