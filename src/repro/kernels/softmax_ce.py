"""Pallas TPU fused output layer: projection, softmax and cross-entropy
gradient of the BLSTM's 32k-way CD-state classifier in one kernel.

The model's last two ops are ``logits = z @ W + b`` over every frame and
the frame-level cross-entropy of those logits.  Through XLA the step
writes the (rows, V) logits to HBM in f32 and bf16 and reads the bf16
copy back four times (logsumexp, dz, dW, db): at the paper's cell (4
learners x 5376 frames x 32,000 states) about 9.7 GB a step, for a
result that is a scalar and three gradients.  Here each tile of ``ti``
frames computes its whole logits row in VMEM and never writes it out:

  grid = (R // ti,)    (learners ride a leading grid axis under vmap)
  resident (one buffer): W (K, V) bf16, b (1, V) f32, db (1, V) f32,
                         the bf16 dW output (K, V);
  streamed: z (ti, K), labels and row weights (ti, 1), nll (ti, 1),
            dz (ti, K);
  scratch:  the f32 dW accumulator (K, V), the tile's f32 exponentials
            (ti, V) and each chunk's row max.

Per row tile, over vocabulary chunks of ``vc`` columns:

1. ``l = z @ W[:, c] + b[c]`` with f32 accumulation; the chunk's row max
   ``m_c``; ``e = exp(l - m_c)`` kept in scratch; the running logsumexp
   and the gold logit;
2. ``d = (e * exp(m_c - lse) - onehot(label)) * row_weight`` in f32 (db
   sums it), rounded to bf16 only as an MXU operand: ``dz += d @ W[:,
   c]^T`` and ``dW[:, c] += z^T @ d`` into the f32 accumulator, which
   leaves as bf16 after the last tile.

Three matmul passes (logits, dz, dW), the least the gradient needs, and
one ``exp`` per logit.  Precision is the XLA path's or better: logits
and softmax in f32 (XLA rounds the dot to bf16 before the bias add),
bf16 MXU operands, f32 accumulators; dz and dW leave the kernel in the
activation and parameter dtype (bf16).

``softmax_ce`` returns the scalar loss through a ``jax.custom_vjp``: the
``fwd`` rule runs the kernel (``softmax_ce_train``), which computes the
gradients for a unit cotangent, and ``bwd`` scales them by the
cotangent (the loss is the last op, so this is exact).  The primal rule
runs the same kernel and keeps only the loss: training is its one
caller, and evaluation takes the logits of ``forward()``.  Both rules
count their traces in ``repro.obs`` (``kernels/softmax_ce``,
``rule=primal|train``).  Oracle: ``repro.models.common.cross_entropy`` of ``z @ W +
b`` (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.lstm_cell import _resolve_interpret

ROW_TILE = 128            # frames per grid step
VOCAB_CHUNK = 16000       # upper bound of the in-kernel vocabulary chunk
_LANE = 128
_ONE = pl.Buffered(1)     # grid-invariant blocks: a single VMEM buffer


def supported(vocab: int) -> bool:
    """Whether the kernel takes a vocabulary of this size (lane-tiled)."""
    return vocab % _LANE == 0


def _vocab_chunk(vocab: int) -> int:
    """Largest multiple of 128 that divides ``vocab`` and is at most
    :data:`VOCAB_CHUNK` (16,000 for the paper's 32,000 states)."""
    n = vocab // _LANE
    return _LANE * max(k for k in range(1, n + 1)
                       if n % k == 0 and k * _LANE <= VOCAB_CHUNK)


def _row_tile(rows: int) -> int:
    """:data:`ROW_TILE`, or the 16-row multiple that covers fewer rows."""
    return min(ROW_TILE, -(-rows // 16) * 16)


# The whole VMEM of a v5e TensorCore: the kernel keeps W, the bf16 dW
# output, its f32 accumulator and the tile's f32 exponentials resident
# (about 82 MB at the paper's 256 x 32,000), and a limit is a ceiling,
# not a reservation.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=128 * 2 ** 20)


def _hit(lab, c, ti, vc):
    """(ti, vc) mask of the gold column inside chunk ``c``."""
    col = jax.lax.broadcasted_iota(jnp.int32, (ti, vc), 1)
    return col == lab - c * vc


def _chunk(c, vc):
    """Columns of vocabulary chunk ``c`` (lane-aligned)."""
    return pl.ds(pl.multiple_of(c * vc, _LANE), vc)


def _logits(z, w_ref, b_ref, sl):
    return jax.lax.dot_general(
        z, w_ref[:, sl], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[:, sl]


def _make_train_kernel(nc: int, vc: int):
    """Forward and gradient of one row tile (module docstring)."""

    def kernel(z_ref, w_ref, b_ref, lab_ref, rw_ref,
               nll_ref, dz_ref, dw_ref, db_ref, e_ref, mc_ref, acc_ref):
        i = pl.program_id(0)
        ti = z_ref.shape[0]

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            db_ref[...] = jnp.zeros_like(db_ref)

        z, lab, rw = z_ref[...], lab_ref[...], rw_ref[...]

        def forward(c, carry):                  # 1: logits, exp, sums
            m, s, gold = carry
            sl = _chunk(c, vc)
            lg = _logits(z, w_ref, b_ref, sl)
            mc = jnp.max(lg, axis=1, keepdims=True)
            e = jnp.exp(lg - mc)
            e_ref[:, sl] = e
            mc_ref[c] = mc
            m_new = jnp.maximum(m, mc)
            s = (s * jnp.exp(m - m_new)
                 + jnp.sum(e, axis=1, keepdims=True) * jnp.exp(mc - m_new))
            gold += jnp.sum(jnp.where(_hit(lab, c, ti, vc), lg, 0.0),
                            axis=1, keepdims=True)
            return m_new, s, gold

        col = jnp.zeros((ti, 1), jnp.float32)
        m, s, gold = jax.lax.fori_loop(0, nc, forward,
                                       (col - jnp.inf, col, col))
        lse = m + jnp.log(s)
        nll_ref[...] = lse - gold

        def grads(c, dz):                       # 2: gradients
            sl = _chunk(c, vc)
            # (softmax - onehot) * row weight; softmax = e * exp(mc - lse)
            d = (e_ref[:, sl] * (rw * jnp.exp(mc_ref[c] - lse))
                 - jnp.where(_hit(lab, c, ti, vc), rw, 0.0))
            db_ref[:, sl] += jnp.sum(d, axis=0, keepdims=True)
            d = d.astype(w_ref.dtype)
            acc_ref[:, sl] += jax.lax.dot_general(
                z, d, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dz + jax.lax.dot_general(
                d, w_ref[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        dz = jax.lax.fori_loop(0, nc, grads,
                               jnp.zeros(dz_ref.shape, jnp.float32))
        dz_ref[...] = dz.astype(dz_ref.dtype)

        @pl.when(i == pl.num_programs(0) - 1)
        def _flush():
            dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)

    return kernel


def _pad_rows(a, rows):
    return jnp.pad(a, [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _run_train(z, w, b, labels, rw, *, interpret):
    """Kernel -> (nll (R,), dz (R, K), dW (K, V), db (V,) f32) for the
    loss ``sum(nll * rw)``."""
    (R, K), V = z.shape, w.shape[1]
    ti, vc = _row_tile(R), _vocab_chunk(V)
    Rp = -(-R // ti) * ti
    rows = pl.BlockSpec((ti, 1), lambda i: (i, 0))
    nll, dz, dw, db = pl.pallas_call(
        _make_train_kernel(V // vc, vc),
        grid=(Rp // ti,),
        in_specs=[pl.BlockSpec((ti, K), lambda i: (i, 0)),
                  pl.BlockSpec((K, V), lambda i: (0, 0), pipeline_mode=_ONE),
                  pl.BlockSpec((1, V), lambda i: (0, 0), pipeline_mode=_ONE),
                  rows, rows],
        out_specs=[
            rows,
            pl.BlockSpec((ti, K), lambda i: (i, 0)),
            pl.BlockSpec((K, V), lambda i: (0, 0), pipeline_mode=_ONE),
            pl.BlockSpec((1, V), lambda i: (0, 0), pipeline_mode=_ONE),
        ],
        out_shape=[jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, K), z.dtype),
                   jax.ShapeDtypeStruct((K, V), w.dtype),
                   jax.ShapeDtypeStruct((1, V), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((ti, V), jnp.float32),
                        pltpu.VMEM((V // vc, ti, 1), jnp.float32),
                        pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_resolve_interpret(interpret),
        name="softmax_ce_train",
    )(_pad_rows(z, Rp), w, b.reshape(1, V).astype(jnp.float32),
      _pad_rows(labels.reshape(R, 1).astype(jnp.int32), Rp),
      _pad_rows(rw.reshape(R, 1).astype(jnp.float32), Rp))
    return nll[:R, 0], dz[:R], dw, db[0]


def _loss(nll, rw):
    return jnp.sum(nll * rw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ce(interpret, z, w, b, labels, rw):
    obs.counter("kernels/softmax_ce", rule="primal").inc()
    return _loss(_run_train(z, w, b, labels, rw, interpret=interpret)[0], rw)


def _ce_fwd(interpret, z, w, b, labels, rw):
    obs.counter("kernels/softmax_ce", rule="train").inc()
    nll, dz, dw, db = _run_train(z, w, b, labels, rw, interpret=interpret)
    return _loss(nll, rw), (dz, dw, db.astype(b.dtype), labels, rw)


def _ce_bwd(interpret, res, g):
    dz, dw, db, labels, rw = res
    return ((dz * g).astype(dz.dtype), (dw * g).astype(dw.dtype),
            (db * g).astype(db.dtype),
            np.zeros(labels.shape, jax.dtypes.float0), jnp.zeros_like(rw))


_ce.defvjp(_ce_fwd, _ce_bwd)


def softmax_ce(z, w, b, labels, mask=None, *, interpret: bool = None):
    """Mean frame cross-entropy of ``z @ w + b`` against ``labels``.

    z (..., K) activations, w (K, V) and b (V,) the output layer, labels
    (...) int.  With ``mask`` (bool, labels' shape) the loss is the sum
    over valid frames over their count, as
    :func:`repro.models.common.cross_entropy`; without it the mean.
    Differentiable in z, w and b; V must be a multiple of 128
    (:func:`supported`)."""
    K, V = w.shape
    if not supported(V):
        raise ValueError(f"vocabulary {V} is not a multiple of {_LANE}")
    z = z.reshape(-1, K)
    labels = labels.reshape(-1)
    if mask is None:
        rw = jnp.full(labels.shape, 1.0 / labels.shape[0], jnp.float32)
    else:
        m = mask.reshape(-1).astype(jnp.float32)
        rw = m / jnp.maximum(jnp.sum(m), 1.0)
    return _ce(interpret, z, w, b, labels, rw)
