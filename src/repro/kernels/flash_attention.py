"""Pallas TPU attention that trains: forward and backward kernels under
one ``jax.custom_vjp``, with each query block's whole key row in VMEM.

Through XLA, attention writes every query chunk's f32 scores to HBM and
reads them back for the softmax, the PV product and, in the backward,
for dP and dS: at whisper's encoder (8 x 20 heads x 1500 x 1500) about
0.5 GB of scores per 512-query chunk and layer, and about 3 GB a chunk
in the backward.  Here the scores never leave VMEM.  The key row is
short enough to hold whole (1,500 keys of 64 bf16 lanes are 0.2 MB a
head), so the softmax is exact over the full row and needs no online
rescaling.

Layout.  q, k, v, o and their gradients travel feature-major, as
(B, H * E, S): the physical layout XLA gives the model's projections
with 64-wide heads on a TPU (sequence minor), so no copy or pad of them
reaches HBM.  A block is 128 feature rows (two heads of 64) by
``block_q`` queries, or by every key for k and v; each head is picked by
zeroing the other head's rows of q (and of dO), which costs the MXU
nothing, since a contraction of 64 takes a pass of 128 anyway.  A query
length off the block leaves a partial last block, whose columns past the
end are zeroed on load and never stored; keys are never cut.

  grid = (B, H * E / 128, ceil(Sq / block_q))    (learners lead under vmap)

``attn_fwd``, per head of the block:
  s = q_h k^T * scale (bf16 operands, f32 accumulation, f32 scores),
  p = exp(s - max) / sum over the whole row (f32),
  o_h = bf16(p) v (f32 accumulation);
  it writes o and the row's log-sum-exp (f32, lane-dense (B, H, Sq)).

``attn_bwd``, per head of the block, from q, k, v, o, dO and the saved
log-sum-exp, with the scores transposed to (Sk, bq) so that the saved
log-sum-exp and rowsum(dO o) broadcast as rows (k and v are transposed
once per head pair into VMEM):
  p^T = exp(k q_h^T * scale - lse) (the forward's f32 scores),
  dP^T = v dO_h^T (f32),  dS^T = p^T (dP^T - rowsum(dO_h o_h)) (f32),
  dQ_h^T = k^T bf16(dS^T),  dK += bf16(dS^T) q_h,  dV += bf16(p^T) dO_h;
  dK and dV accumulate in f32 VMEM over the query blocks of one head
  pair (the last grid axis) and are written once.

Precision is that of the XLA softmax path or better: bf16 MXU operands,
f32 scores, softmax, dP and dS (XLA rounds the score and dP matmuls to
bf16), bf16 p into PV and dV, bf16 dS into dQ and dK.  Causal masking is
by position; there is no block skip.

:func:`flash_attention` differentiates in q, k and v.  Its forward rule
names its two residuals besides q, k and v, the output (``attn_out``)
and the log-sum-exp (``attn_lse``), with ``checkpoint_name``, so that a
caller's remat policy can keep them: otherwise the backward reruns
``attn_fwd`` to get them back.  The kernels compile for a TPU v5e at
whisper-large-v3's shapes (``tests/test_tpu_compile.py``); the oracles
are ``repro.kernels.ref.attention_ref`` and the model's XLA path
(``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lstm_cell import _resolve_interpret

LANES = 128
BLOCK_Q = 512                     # queries per grid step
OUT_NAME, LSE_NAME = "attn_out", "attn_lse"
NEG_INF = -1e30

_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2 ** 20)


def supported(heads: int, head_dim: int) -> bool:
    """Whether a block of 128 feature rows (or of ``head_dim`` rows, a
    multiple of 128) holds whole heads, and the heads fill whole
    blocks."""
    if head_dim % LANES == 0:
        return True
    return LANES % head_dim == 0 and heads % (LANES // head_dim) == 0


def block_rows(seq_q: int, block_q: int = BLOCK_Q) -> int:
    """Queries of a grid step: ``block_q`` (a multiple of 128), or the one
    128 multiple that covers a shorter query."""
    if block_q % LANES:
        raise ValueError(f"block_q {block_q} is not a multiple of {LANES}")
    return min(block_q, -(-seq_q // LANES) * LANES)


def _nn(a, b):
    """a (m, c) times b (c, n), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """a (c, m)^T times b (c, n), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nt(a, b):
    """a (m, c) times b (n, c)^T, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _row(col):
    """(n, 1) column -> (1, n) row, through an aligned transpose."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[0:1, :]


def _live(x, i, seq_q):
    """Block ``i``'s (rows, bq) columns, zero past the query length (a
    partial last block reads past the array)."""
    bq = x.shape[1]
    if seq_q % bq == 0:
        return x
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
    return jnp.where(col < seq_q - i * bq, x, jnp.zeros_like(x))


def _head_of(head_dim, shape, axis):
    """Each feature's head within a block, along ``axis`` of ``shape``."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis) // head_dim


def _causal(s, q_axis, q_start):
    """Mask the keys after each query; queries along ``q_axis`` of s."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, head_dim, causal,
                scale, seq_q):
    i = pl.program_id(2)
    rows, bq = q_ref.shape
    g = rows // head_dim
    qt, kt, vt = _live(q_ref[...], i, seq_q), k_ref[...], v_ref[...]
    row_head = _head_of(head_dim, (rows, 1), 0)
    o = jnp.zeros((rows, bq), jnp.float32)
    for h in range(g):
        qh = qt if g == 1 else jnp.where(row_head == h, qt,
                                         jnp.zeros_like(qt))
        s = _tn(qh, kt) * scale                          # (bq, Sk)
        if causal:
            s = _causal(s, 0, i * bq)
        m = jnp.max(s, axis=1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=1, keepdims=True)
        pv = _nt(vt, (e * (1.0 / l)).astype(vt.dtype))   # (rows, bq)
        o = pv if g == 1 else jnp.where(row_head == h, pv, o)
        lse_ref[h:h + 1, :] = _row(m + jnp.log(l))
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, k_nat, v_nat, dk_acc, dv_acc, *,
                head_dim, causal, scale, seq_q):
    i = pl.program_id(2)
    rows, bq = q_ref.shape
    g = rows // head_dim

    @pl.when(i == 0)
    def _init():
        k_nat[...] = k_ref[...].T
        v_nat[...] = v_ref[...].T
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qt, dot = _live(q_ref[...], i, seq_q), _live(do_ref[...], i, seq_q)
    do_o = (dot.astype(jnp.float32)
            * _live(o_ref[...], i, seq_q).astype(jnp.float32))
    lse = _live(lse_ref[...], i, seq_q)
    kt, k, v = k_ref[...], k_nat[...], v_nat[...]
    row_head = _head_of(head_dim, (rows, 1), 0)
    dq = jnp.zeros((rows, bq), jnp.float32)
    for h in range(g):
        if g == 1:
            qh, doh, dh = qt, dot, do_o
        else:
            sel = row_head == h
            qh = jnp.where(sel, qt, jnp.zeros_like(qt))
            doh = jnp.where(sel, dot, jnp.zeros_like(dot))
            dh = jnp.where(sel, do_o, 0.0)
        st = _nn(k, qh) * scale                          # (Sk, bq)
        if causal:
            st = _causal(st, 1, i * bq)
        pt = jnp.exp(st - lse[h:h + 1, :])
        delta = jnp.sum(dh, axis=0, keepdims=True)       # (1, bq)
        dst = (pt * (_nn(v, doh) - delta)).astype(k.dtype)
        dqh = _nn(kt, dst)                               # (rows, bq)
        dq = dqh if g == 1 else jnp.where(sel, dqh, dq)
        dk_acc[...] += _nt(dst, qh)                      # (Sk, rows)
        dv_acc[...] += _nt(pt.astype(dot.dtype), doh)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)

    @pl.when(i == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[...] = (dk_acc[...] * scale).T.astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].T.astype(dv_ref.dtype)


def _call(kernel, name, head_dim, causal, block_q, interpret, operands,
          outs, scratch):
    """The ``pallas_call`` of either kernel over feature-major operands,
    each given with its block kind: ``q`` (rows, bq) blocks of a query-
    length array, ``k`` (rows, Sk) of a key-length one, ``lse`` (g, bq)
    of the log-sum-exp.  ``operands[0]`` is q, ``operands[1]`` k."""
    (_, qt), (_, kt) = operands[:2]
    B, HE, Sq = qt.shape
    Sk, rows = kt.shape[2], max(head_dim, LANES)
    bq = block_rows(Sq, block_q)
    spec = {
        "q": pl.BlockSpec((None, rows, bq), lambda b, j, i: (b, j, i)),
        "k": pl.BlockSpec((None, rows, Sk), lambda b, j, i: (b, j, 0)),
        "lse": pl.BlockSpec((None, None, rows // head_dim, bq),
                            lambda b, j, i: (b, j, 0, i))}
    return pl.pallas_call(
        functools.partial(kernel, head_dim=head_dim, causal=causal,
                          scale=float(1.0 / np.sqrt(head_dim)), seq_q=Sq),
        grid=(B, HE // rows, -(-Sq // bq)),
        in_specs=[spec[kind] for kind, _ in operands],
        out_specs=[spec[kind] for kind, _ in outs],
        out_shape=[s for _, s in outs],
        scratch_shapes=[pltpu.VMEM((Sk, rows), dt) for dt in scratch],
        compiler_params=_COMPILER_PARAMS,
        interpret=_resolve_interpret(interpret),
        name=name,
    )(*[x for _, x in operands])


def _forward(qt, kt, vt, head_dim, causal, block_q, interpret):
    """-> o (B, H * E, Sq), lse (B, H / g, g, Sq) f32, g heads a block."""
    B, HE, Sq = qt.shape
    rows = max(head_dim, LANES)
    lse = jax.ShapeDtypeStruct((B, HE // rows, rows // head_dim, Sq),
                               jnp.float32)
    return _call(_fwd_kernel, "attn_fwd", head_dim, causal, block_q,
                 interpret, [("q", qt), ("k", kt), ("k", vt)],
                 [("q", jax.ShapeDtypeStruct(qt.shape, qt.dtype)),
                  ("lse", lse)], [])


def _backward(qt, kt, vt, ot, lse, do, head_dim, causal, block_q,
              interpret):
    return _call(_bwd_kernel, "attn_bwd", head_dim, causal, block_q,
                 interpret,
                 [("q", qt), ("k", kt), ("k", vt), ("q", ot),
                  ("q", do.astype(qt.dtype)), ("lse", lse)],
                 [("q", jax.ShapeDtypeStruct(qt.shape, qt.dtype)),
                  ("k", jax.ShapeDtypeStruct(kt.shape, kt.dtype)),
                  ("k", jax.ShapeDtypeStruct(vt.shape, vt.dtype))],
                 [kt.dtype, vt.dtype, jnp.float32, jnp.float32])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention(qt, kt, vt, head_dim, causal, block_q, interpret):
    return _forward(qt, kt, vt, head_dim, causal, block_q, interpret)[0]


def _attention_fwd(qt, kt, vt, head_dim, causal, block_q, interpret):
    o, lse = _forward(qt, kt, vt, head_dim, causal, block_q, interpret)
    o = checkpoint_name(o, OUT_NAME)
    lse = checkpoint_name(lse, LSE_NAME)
    return o, (qt, kt, vt, o, lse)


def _attention_bwd(head_dim, causal, block_q, interpret, res, do):
    return _backward(*res, do, head_dim, causal, block_q, interpret)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = BLOCK_Q,
                    interpret: bool = None):
    """q: (B, Sq, H, E); k/v: (B, Sk, KV, E) -> (B, Sq, H, E).

    Differentiable in q, k and v.  Grouped k/v heads (KV < H) are
    repeated to H heads first, so their gradients sum over the group.
    Needs :func:`supported` (H, E); ``block_q`` is a multiple of 128."""
    B, Sq, H, E = q.shape
    if not supported(H, E):
        raise ValueError(f"{H} heads of {E} do not fill {LANES}-row blocks")
    if k.shape[2] != H:
        k, v = (jnp.repeat(t, H // k.shape[2], axis=2) for t in (k, v))

    def features(t):                       # (B, S, H, E) -> (B, H * E, S)
        return jnp.swapaxes(t.reshape(t.shape[0], t.shape[1], H * E), 1, 2)

    ot = _attention(features(q), features(k), features(v), E, causal,
                    block_q, interpret)
    return jnp.swapaxes(ot, 1, 2).reshape(B, Sq, H, E)
