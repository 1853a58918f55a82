"""Pallas TPU flash attention (causal / sliding-window GQA).

TPU adaptation of the memory-bound hot-spot the §Roofline analysis flags in
every attention-bearing architecture: the pure-JAX path materializes the
(S x S) score tensor per q-chunk in HBM; this kernel keeps the running
softmax statistics in VMEM and never writes probabilities back.

Grid: (batch, kv_group, q_blocks).  Each program owns one q block of
``block_q`` rows for one (batch, kv-head-group) and streams kv blocks of
``block_k`` through VMEM with the standard online-softmax recurrence
(m: running max, l: running normalizer, acc: f32 accumulator).

Blocks are MXU-aligned (block_q x head_dim and block_k x head_dim tiles,
multiples of 128 on the contracting dim where head_dim allows).  The
kv loop is ``lax.fori_loop`` over kv blocks with a causal upper bound —
blocks fully above the diagonal (or fully outside the sliding window) are
skipped, which is where the sub-quadratic win for windowed layers comes
from.

Validated in interpret mode against ``repro.kernels.ref.attention_ref``
(tests/test_kernels.py sweeps shapes, dtypes, windows, GQA ratios).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, seq_k: int,
                 causal: bool, window: int, q_offset: int, scale: float):
    """One (batch, group, q-block) program.

    q_ref: (block_q, heads_per_group, head_dim) VMEM
    k_ref/v_ref: (seq_k, head_dim) VMEM (one kv head)
    o_ref: (block_q, heads_per_group, head_dim)
    """
    block_q, m_per_g, head_dim = q_ref.shape
    q_block_idx = pl.program_id(2)
    q_start = q_block_idx * block_q + q_offset

    q = q_ref[...].astype(jnp.float32).reshape(block_q * m_per_g, head_dim)

    n_kv = seq_k // block_k
    if causal:
        # last kv block that intersects [q_start, q_start+block_q)
        hi = jnp.minimum((q_start + block_q - 1) // block_k + 1, n_kv)
    else:
        hi = n_kv
    if causal and window > 0:
        lo = jnp.maximum(q_start - window + 1, 0) // block_k
    else:
        lo = 0

    def body(kb, carry):
        acc, m_i, l_i = carry
        rows = pl.ds(kb * block_k, block_k)
        k = k_ref[rows, :].astype(jnp.float32)
        v = v_ref[rows, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq*m, bk)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, m_per_g), 0).reshape(block_q * m_per_g)
            k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
            ok = q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                ok &= (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q * m_per_g, head_dim), jnp.float32)
    m0 = jnp.full((block_q * m_per_g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q * m_per_g,), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l_i, 1e-30)[:, None]
    o_ref[...] = out.reshape(block_q, m_per_g, head_dim).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512,
                    q_offset: int = 0, interpret: bool = None):
    """q: (B, Sq, H, E); k/v: (B, Sk, KV, E) -> (B, Sq, H, E).

    GQA: each kv head serves H//KV query heads; grid axis 1 walks kv heads
    and the q block carries its group's query heads together (better MXU
    utilization than one head at a time when H//KV > 1).
    """
    B, Sq, H, E = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    M = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    qg = q.reshape(B, Sq, KV, M, E)
    grid = (B, KV, Sq // block_q)
    kernel = functools.partial(
        _attn_kernel, block_k=block_k, seq_k=Sk, causal=causal,
        window=int(window), q_offset=int(q_offset),
        scale=float(1.0 / np.sqrt(E)))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, None, M, E),
                         lambda b, g, i: (b, i, g, 0, 0)),
            pl.BlockSpec((None, Sk, None, E), lambda b, g, i: (b, 0, g, 0)),
            pl.BlockSpec((None, Sk, None, E), lambda b, g, i: (b, 0, g, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, None, M, E),
                               lambda b, g, i: (b, i, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, KV, M, E), q.dtype),
        interpret=interpret,
    )(qg, k, v)
    return out.reshape(B, Sq, H, E)
