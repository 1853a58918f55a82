"""Pallas TPU kernel for the prefix-beam inner step (+ decode argmax).

The hot loop of CTC beam decoding is the per-frame candidate expansion,
duplicate merge and top-K over the ``beam x vocab`` candidate grid —
O(K·V) scores plus K argmax passes per frame, latency-bound at serving
batch sizes.  :func:`beam_frame_step` runs that step as one Pallas
kernel: the (bB, V) frame log-probs and the six (bB, K) beam-state
vectors are VMEM-resident blocks on a ``(B // bB,)`` batch grid, and
every intermediate (the (bB, K, V) extend scores, the (bB, K, K) merge
match, the (bB, K*V) candidate grid the K argmax passes sweep) lives in
VMEM for the whole step — nothing round-trips HBM between expansion and
selection.

The kernel body calls ``repro.decode.beam.frame_step_scores`` — the
*same* array math as the jnp path — so pallas-vs-jax parity is
bit-for-bit by construction (the tests still assert it, in interpret
mode, like every other kernel in this repo).  The state *update* (token
append, hash/length bookkeeping) stays in jnp outside the kernel: it is
O(K·U) gathers with no V-sized intermediates.

VMEM math (docs/decoding.md, single source :func:`beam_cand_bytes`):
the unpruned resident set per grid step is about ``bB*V*4`` (logp)
+ ``3 * bB*K*V*4`` (base/ext/candidate grids) + small (bB, K) vectors —
for (bB=8, K=8, V=512) about 0.5 MB — and the default ``block_b`` is
picked by :func:`auto_block_b_decode` so the set fits the 12 MB budget
of kernels that keep Mosaic's default scoped VMEM limit.  ``topc=C`` swaps the body for
``frame_step_scores_topc``: the K-scaled grids shrink from (K, V) to
(K, C+1) and vocab survives only in the logp block + top-C sweep
workspace, so the VMEM ceiling (and hence ``block_b``) stops scaling
with vocab — the hard ceiling the unpruned kernel put on V.  Off-TPU the
kernel executes in interpret mode (CI parity path); the gathers inside
``frame_step_scores`` are interpret-validated, compiled-TPU lowering is
tracked with the other real-TPU items in ROADMAP.md.

:func:`argmax_tokens` is the degenerate beam=1 selector — a one-pass
VMEM argmax over (bB, V) logits.  ``launch/serve.py`` routes its
one-token LM decode loop through it under ``--kernel-impl pallas``
(bit-identical to ``jnp.argmax``), so the flag finally covers the whole
request loop, not just prefill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.decode.beam import (NEG, frame_step_scores,
                               frame_step_scores_topc)
from repro.kernels.lstm_cell import (SCOPED_VMEM_BUDGET,
                                     _resolve_interpret)


def beam_cand_bytes(beam: int, vocab: int, topc: int = 0) -> int:
    """f32 bytes per batch row of the beam-step candidate working set —
    the single source of the VMEM accounting (docs/decoding.md, the
    ``--only serve`` bench).  Unpruned: ~4 live (K, V) grids
    (base/ext/candidate/argmax sweep) + the (V,) logp block.  With
    top-C pruning the K-scaled grids shrink to (K, C+1) — vocab only
    enters through the logp block and its top-C sweep workspace, so the
    candidate memory scales with C, not V."""
    if topc and topc < vocab:
        return (4 * beam * (topc + 1) + 2 * vocab + 2 * topc) * 4
    return (4 * beam * vocab + vocab) * 4


def auto_block_b_decode(B: int, beam: int, vocab: int,
                        vmem_budget: int = None, topc: int = 0) -> int:
    """Largest batch tile whose beam-step resident set
    (:func:`beam_cand_bytes`) fits the budget."""
    budget = vmem_budget or SCOPED_VMEM_BUDGET
    per_row = beam_cand_bytes(beam, vocab, topc)
    bb = max(1, budget // max(per_row, 1))
    return int(min(bb, B))


def beam_frame_step(logp, p_b, p_nb, last, phash, plen, *, blank: int,
                    max_len: int, semiring: str, block_b: int = None,
                    interpret=None, topc: int = 0):
    """Pallas-resident ``beam.frame_step_scores``: same signature and
    bit-identical outputs ``(sel, new_pb, new_pnb)``.  ``topc`` > 0
    runs the fused top-C pruned step (``frame_step_scores_topc``): the
    top-C sweep AND the pruned candidate grid live in one kernel, so
    the (bB, K, V) grids never materialize."""
    B, V = logp.shape
    K = p_b.shape[1]
    interpret = _resolve_interpret(interpret)
    topc = 0 if topc >= V else topc
    bb = block_b or auto_block_b_decode(B, K, V, topc=topc)
    bb = max(1, min(bb, B))

    pad = (-B) % bb
    if pad:
        logp = jnp.pad(logp, ((0, pad), (0, 0)))
        p_b = jnp.pad(p_b, ((0, pad), (0, 0)), constant_values=NEG)
        p_nb = jnp.pad(p_nb, ((0, pad), (0, 0)), constant_values=NEG)
        last = jnp.pad(last, ((0, pad), (0, 0)), constant_values=-1)
        phash = jnp.pad(phash, ((0, pad), (0, 0)))
        plen = jnp.pad(plen, ((0, pad), (0, 0)))
    Bp = B + pad

    def kernel(logp_ref, pb_ref, pnb_ref, last_ref, hash_ref, len_ref,
               sel_ref, npb_ref, npnb_ref):
        if topc:
            sel, npb, npnb = frame_step_scores_topc(
                logp_ref[:], pb_ref[:], pnb_ref[:], last_ref[:],
                hash_ref[:], len_ref[:], blank=blank, max_len=max_len,
                semiring=semiring, topc=topc)
        else:
            sel, npb, npnb = frame_step_scores(
                logp_ref[:], pb_ref[:], pnb_ref[:], last_ref[:],
                hash_ref[:], len_ref[:], blank=blank, max_len=max_len,
                semiring=semiring)
        sel_ref[:] = sel
        npb_ref[:] = npb
        npnb_ref[:] = npnb

    row = lambda i: (i, 0)
    spec_v = pl.BlockSpec((bb, V), row, memory_space=pltpu.VMEM)
    spec_k = pl.BlockSpec((bb, K), row, memory_space=pltpu.VMEM)
    sel, npb, npnb = pl.pallas_call(
        kernel,
        grid=(Bp // bb,),
        in_specs=[spec_v, spec_k, spec_k, spec_k, spec_k, spec_k],
        out_specs=(spec_k, spec_k, spec_k),
        out_shape=(
            jax.ShapeDtypeStruct((Bp, K), jnp.int32),
            jax.ShapeDtypeStruct((Bp, K), jnp.float32),
            jax.ShapeDtypeStruct((Bp, K), jnp.float32),
        ),
        interpret=interpret,
    )(logp, p_b, p_nb, last, phash, plen)
    if pad:
        sel, npb, npnb = sel[:B], npb[:B], npnb[:B]
    return sel, npb, npnb


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def argmax_tokens(logits, *, interpret=None, block_b: int = None):
    """(B, V) logits -> (B,) i32 argmax via a VMEM kernel — the beam=1
    token selector of the serving decode loop (bit-matches
    ``jnp.argmax(logits, -1)``)."""
    B, V = logits.shape
    interpret = _resolve_interpret(interpret)
    bb = max(1, min(block_b or B, B))
    pad = (-B) % bb
    if pad:
        logits = jnp.pad(logits, ((0, pad), (0, 0)), constant_values=NEG)
    Bp = B + pad

    def kernel(x_ref, out_ref):
        out_ref[:] = jnp.argmax(
            x_ref[:].astype(jnp.float32), axis=1, keepdims=True
        ).astype(jnp.int32)

    out = pl.pallas_call(
        kernel,
        grid=(Bp // bb,),
        in_specs=[pl.BlockSpec((bb, V), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((bb, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        interpret=interpret,
    )(logits)
    return out[:B, 0]
