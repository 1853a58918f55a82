"""Pallas TPU fused (B)LSTM sequence kernels — the training hot path.

The paper's acoustic model spends essentially all of its compute in 6
bi-LSTM layers (Table I: 165MB model, 0.07 s/batch); every distributed
strategy in §IV only pays off if this per-learner step is fast.  A
time-step of LSTM is two skinny matmuls plus elementwise gates —
dominated by weight re-reads from HBM if each step round-trips.  The TPU
adaptation keeps the weight matrices and the recurrent (h, c) state
resident in VMEM across the whole unroll and walks time on the inner
sequential grid axis, so HBM traffic per step is just x_t in / h_t out.

The kernels are TIME-MAJOR: every sequence array is (T, B, feature) and
every streamed block is ``(None, bB, feature)`` — time squeezed on the
leading, untiled axis, so the last two block dims are (batch tile,
features), which is the (8, 128) tiling Mosaic requires:

  grid = (B//bB, T);  VMEM blocks per direction:
      x_t (bB, D), Wx (D, 4H), Wh (H, 4H), b (1, 4H); scratch h, c (bB, H).

The public entry points keep the batch-major (B, T, D) contract and
transpose once at the boundary.  The batch axis is tiled with
``block_b`` (``bB``): the time axis is the *inner* (fastest-varying)
grid axis so each batch tile walks the whole recurrence with its own
resident (h, c) carry before the grid moves to the next tile — an
outer-batch grid would need every tile's state live at once and defeat
the tiling.  Batches that are not a multiple of ``block_b`` are
zero-padded up front and sliced after; padded rows never pollute weight
gradients because their output cotangents are zero.

Gate layout (i|f|g|o) matches ``repro.models.lstm.lstm_cell_step``, which
is the oracle via ``repro.kernels.ref.lstm_ref`` (forget-gate bias +1).

Variable-length masking (``lengths``)
-------------------------------------
Passing a per-row ``lengths`` (B,) int32 vector (the batch contract of
``repro.data.pipeline``) selects the masked kernels: the vector enters
as a (B, 1) column whose (bB, 1) block rides along the batch grid axis,
and on padded steps (time >= lengths[row]) the (h, c) VMEM carry is
FROZEN and the emitted h_t is zero, so padded frames can never leak into
weight gradients.  The reverse direction thereby reverses *within* each
utterance's valid span: its leading invalid segment (right-padding)
carries the zero initial state untouched until the last valid frame.
The backward kernel mirrors this — on invalid steps dgates are zeroed
and the (dh, dc) carries pass through unchanged.  Rows added by
batch-tile padding get length 0, which subsumes the zero-cotangent
argument above.  Oracle: ``repro.kernels.ref.lstm_ref(..., lengths=...)``
(masked scan).

Three kernel variants share one body (``_make_fwd_kernel``):

* inference forward (``stash=False``) — emits h_t only;
* training forward (``stash=True``) — additionally stashes the
  post-activation gates (bB, 4H) and cell states (bB, H) per step, f32;
* bidirectional fusion (``n_dir=2``) — both directions advance in one
  grid pass (forward direction at time t, reverse direction at T-1-t),
  with both weight sets resident in VMEM and x handed to the kernel
  once; per-direction math is op-for-op identical to the ``n_dir=1``
  kernel, so the fused output is bit-identical to two separate calls.

Backward pass (``_make_bwd_kernel``)
------------------------------------
Wired via ``jax.custom_vjp`` so ``jax.value_and_grad`` through
``models/lstm.loss_train(kernel_impl="pallas")`` works end-to-end.  The
backward kernel walks the time grid in *reverse recurrence order*,
carrying (dh, dc) in VMEM scratch and accumulating dWx (D, 4H),
dWh (H, 4H) and db (1, 4H) in f32 VMEM-resident output blocks (constant
index maps — the block is zeroed at the first grid program and flushed
once at the end), while emitting dx_t per step.  h_{t-1} is re-read from
the stashed forward output y (the value that actually entered the
recurrent matmul, post bf16 rounding), c_{t-1}/c_t from the stashed cell
states, and the gate nonlinearities come from the stashed activations —
only tanh(c_t) is recomputed.

Residual stashing vs recompute
------------------------------
We stash post-activation gates + cell states, by default in f32:
4H + H = 5H floats per (row, step) — for the paper shape
(B=256, T=21, H=512) that is 256*21*5*512*4B ≈ 55MB HBM per direction,
written once in the forward and read once in the backward.
``stash_dtype="bfloat16"`` halves that stash (gates are in [-1, 1] so
bf16's 8 relative bits cost ~1e-2 normalized grad error — the relaxed
tolerance of the parity test); the backward upcasts to f32 on read and
its dW accumulators stay f32 either way.  The
alternative — recomputing gates in the backward — saves that HBM
traffic but re-runs both matmuls (2/3 of the step FLOPs) and still has
to stash or recompute the cell-state sequence for df/dc; on TPU the
matmul units are the scarce resource for this skinny shape, so we trade
HBM capacity for MXU time (same choice cuDNN makes) *at the paper's
T=21*.  For long utterances that trade flips — see next section.

Sequence-chunked recompute (``seq_chunk``)
------------------------------------------
Conversational utterances run to thousands of frames; an O(T) residual
stash caps sequence length well below that operating point.  With
``seq_chunk=K`` (> 0, frames per chunk; -1 lets :func:`auto_tile` pick
``(block_b, K)`` jointly from the VMEM budget) the training forward
stashes only the (h, c) carries at each chunk *entry* — 2H floats per
(row, chunk) instead of 5H per (row, step), an O(T) -> O(T/K)
reduction — and the backward kernel walks a ``(B//bB, T/K)`` grid in
reverse chunk order: each grid step re-runs the forward for its K-frame
chunk entirely in VMEM (rebuilding the gate/cell residuals in scratch),
then runs the K reverse-recurrence steps against them, carrying
(dh, dc) across chunks in scratch exactly like the per-step kernel.
Cost: one extra forward pass worth of matmuls, independent of K; K only
trades VMEM (the chunk residual scratch is ``K*bB*6H`` f32) against the
boundary-stash size.  T that doesn't divide by K is zero-padded to the
next multiple and the padded steps masked off via a synthesized
``lengths`` vector, so the chunked path always runs the masked kernels
(lengths = T everywhere reproduces the dense recurrence exactly).
:func:`stash_bytes` is the accounting single-source (benchmarks and the
stash-size tests read it).

Fused multi-layer stack (``blstm_stack_sequence``)
--------------------------------------------------
The stacked BLSTM's inter-layer h traffic round-trips HBM once per
layer.  :func:`blstm_stack_sequence` runs the whole L-layer stack as ONE
kernel on a ``(B//bB, L, T)`` grid: layer l writes its (T, bB, 2H)
output into a VMEM ping-pong buffer that layer l+1 reads directly, so
only layer 0's input and layer L-1's output touch HBM.  (A *streaming*
cross-layer fusion is impossible for bidirectional layers — layer l+1
at time 0 needs layer l's reverse output at time 0, computed at the
last grid step — hence the buffer holds the full T.)  Per-direction
math is op-for-op the single-layer kernel, so the fused stack is
bit-identical to the per-layer loop.  Under ``jax.vjp`` the custom-VJP
rules fall back to the per-layer stashing forwards/backwards (each
layer's output is a residual the backward needs anyway), composing with
``seq_chunk`` and ``lengths``; the fused kernel serves the primal
(inference) call.  See docs/kernels.md for the full contracts.

VMEM budget, the Mosaic limit and ``block_b`` auto-tuning
---------------------------------------------------------
Every LSTM ``pallas_call`` passes ``vmem_limit_bytes = budget +
VMEM_HEADROOM`` (default budget 96MiB; v5e has 128MiB of VMEM per core
and Mosaic's own default scoped limit is only 16MiB).  The tuners pick
the largest power-of-two batch tile whose modelled resident set fits
the budget; the model counts what Mosaic allocates:

* a block whose index map is grid-invariant (weights, bias, the dW/db
  accumulators) once — Mosaic gives it a single buffer;
* every other BlockSpec'd operand twice — the pipeline double-buffers
  streamed inputs and outputs (the fused stack's per-layer weights
  included);
* scratch once;
* the large in-kernel f32 temporaries: gate pre-activations in the
  forward, and in the backward the f32 copies of Wx/Wh plus the dW
  matmul results before they are accumulated.

One bf16 direction of a D=1024 layer is (1024+512)*2048*2B ≈ 6.3MB, its
f32 dW 12.6MB.  At the paper shape (B=256, T=21, H=512) the per-step
training kernels, the inference forward and the fused stack all fit the
default budget at bB=256; the chunked-recompute pair, with its
(K, bB, 6H) f32 residual scratch, tunes to a smaller tile.  The model
stays above what the chip's compiler needs (docs/kernels.md has the
measured table).  A single tile never pads past the 8-row sublane
multiple (B=96 runs as one 96-row tile, not a padded 128-row one).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# budget of the LSTM tuners; the kernels pass it (plus headroom) to Mosaic
DEFAULT_VMEM_BUDGET = 96 * 2 ** 20
# Mosaic internal scratch and temporaries the byte model does not count
VMEM_HEADROOM = 16 * 2 ** 20
# budget of kernels that keep Mosaic's default 16MiB scoped VMEM limit
SCOPED_VMEM_BUDGET = 12 * 2 ** 20


def _resolve_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _compiler_params(vmem_budget):
    """Mosaic parameters of every LSTM ``pallas_call``: the scoped-VMEM
    limit is the tuners' budget plus :data:`VMEM_HEADROOM`."""
    return pltpu.CompilerParams(vmem_limit_bytes=int(
        (vmem_budget or DEFAULT_VMEM_BUDGET) + VMEM_HEADROOM))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _fit_block_b(B: int, usage, budget: int) -> int:
    """The shared batch-tile search of every tuner: start from the
    power-of-two cover of B, halve while ``usage(bb)`` overruns the
    budget, floor at 8 rows (the f32 sublane tile — below that the
    weights themselves are the problem, not the tile), and never pad a
    single tile past the 8-row sublane multiple."""
    bb = max(8, 1 << (max(B, 1) - 1).bit_length())
    while bb > 8 and usage(bb) > budget:
        bb //= 2
    if bb >= B:
        bb = max(8, _round_up(B, 8))
    return bb


# ---------------------------------------------------------------------------
# VMEM byte model (docs/kernels.md "VMEM budget math")
# ---------------------------------------------------------------------------

_LEN_BLOCK = 128 * 4          # one (1,) int32 lengths row, lane-padded
_BIAS_BLOCK = 8 * 4           # one f32 (1, 4H) row per lane, sublane-padded


def _fwd_usage(bb, D, H, itemsize, n_dir, stash_row=0):
    """Forward kernel (inference, per-step or per-chunk stash):
    ``stash_row`` is the stash-output bytes per batch row and direction."""
    resident = n_dir * ((D + H) * 4 * H * itemsize + 4 * H * _BIAS_BLOCK)
    streamed = (n_dir * bb * ((D + H) * itemsize + stash_row)  # x, y, stash
                + bb * _LEN_BLOCK)
    return (resident + 2 * streamed
            + n_dir * 2 * bb * H * 4                    # (h, c) scratch
            + n_dir * 3 * bb * 4 * H * 4)               # f32 gate temps


def _bwd_resident(D, H, itemsize, n_bias):
    """Single-buffered blocks of a backward kernel: W in, f32 dW/db out
    (``n_bias`` = 1 for db, 2 when the bias comes in too)."""
    return (D + H) * 4 * H * (itemsize + 4) + n_bias * 4 * H * _BIAS_BLOCK


def _bwd_temps(bb, D, H):
    """Backward f32 temporaries: Wx/Wh upcasts, the dW matmul results
    before accumulation, and the (bb, 4H) dgates working set."""
    return 2 * (D + H) * 4 * H * 4 + 4 * bb * 4 * H * 4


def _bwd_usage(bb, D, H, itemsize, stash_itemsize):
    """Per-step backward kernel (one direction)."""
    streamed = (bb * (2 * H + 2 * D) * itemsize         # dy, h_prev, x, dx
                + bb * 6 * H * stash_itemsize           # acts, c, c_prev
                + bb * _LEN_BLOCK)
    return (_bwd_resident(D, H, itemsize, 1) + 2 * streamed
            + 2 * bb * H * 4 + _bwd_temps(bb, D, H))


def _bwd_chunked_usage(bb, K, D, H, itemsize, stash_itemsize):
    """Chunked-recompute backward kernel (one direction)."""
    streamed = (K * bb * (H + 2 * D) * itemsize         # dy, x, dx chunks
                + 2 * bb * H * stash_itemsize           # entry carries
                + bb * _LEN_BLOCK)
    scratch = K * bb * 6 * H * 4 + 2 * bb * H * 4       # residuals, dh/dc
    return (_bwd_resident(D, H, itemsize, 2) + 2 * streamed + scratch
            + _bwd_temps(bb, D, H))


def auto_block_b(B: int, D: int, H: int, itemsize: int, *, n_dir: int = 1,
                 training: bool = False, vmem_budget: int = None,
                 stash_itemsize: int = 4) -> int:
    """Largest power-of-two batch tile whose modelled resident set fits
    the VMEM budget (module docstring).  Training takes the worse of the
    stashing forward (all directions) and the backward (one direction).
    Floors at 8 rows (the f32 sublane tile) even when the budget is
    overrun — at that point the weights themselves are the problem, not
    the tile.  ``stash_itemsize`` reflects the gate/cell residual stash
    dtype (2 for the bf16 stash option)."""
    budget = vmem_budget or DEFAULT_VMEM_BUDGET

    def usage(bb):
        if not training:
            return _fwd_usage(bb, D, H, itemsize, n_dir)
        return max(_fwd_usage(bb, D, H, itemsize, n_dir,
                              5 * H * stash_itemsize),
                   _bwd_usage(bb, D, H, itemsize, stash_itemsize))

    return _fit_block_b(B, usage, budget)


def stash_bytes(B: int, T: int, H: int, *, n_dir: int = 1,
                stash_itemsize: int = 4, seq_chunk: int = 0) -> int:
    """Residual-stash HBM bytes of the training forward (the accounting
    single-source for benchmarks/run.py --only longseq and the stash-size
    tests).  Unchunked: post-activation gates (4H) + cell states (H) per
    (row, step).  Chunked: only the (h, c) chunk-entry carries — 2H per
    (row, chunk), ceil(T / seq_chunk) chunks after time padding."""
    if seq_chunk and seq_chunk > 0:
        n_chunks = -(-T // seq_chunk)
        return n_dir * B * n_chunks * 2 * H * stash_itemsize
    return n_dir * B * T * 5 * H * stash_itemsize


def _chunked_usage(bb, K, D, H, itemsize, n_dir, stash_itemsize):
    """Worst single-kernel VMEM resident set of the chunked training pair
    (chunk-stash forward vs chunked-recompute backward) — the byte math
    behind :func:`auto_tile`; docs/kernels.md walks through it."""
    return max(_fwd_usage(bb, D, H, itemsize, n_dir, 2 * H * stash_itemsize),
               _bwd_chunked_usage(bb, K, D, H, itemsize, stash_itemsize))


def auto_tile(B: int, T: int, D: int, H: int, itemsize: int, *,
              n_dir: int = 1, vmem_budget: int = None,
              stash_itemsize: int = 4, seq_chunk: int = -1,
              block_b: int = None):
    """Jointly pick ``(block_b, seq_chunk)`` for the chunked TRAINING
    kernels so the worse of (chunk-stash forward, chunked-recompute
    backward) fits the VMEM budget.

    ``seq_chunk > 0`` fixes the chunk length (clamped to T) and only
    ``block_b`` is tuned; ``seq_chunk = -1`` starts from
    min(256, next_pow2(T)) and halves the chunk first (chunk length only
    trades VMEM — the recompute cost is one extra forward pass regardless
    of K), then the batch tile, flooring at K=16 frames and bb=8 rows;
    finally K is halved further while the time padding it induces
    (round_up(T, K) - T) exceeds T/8, so an unlucky T cannot waste a
    large fraction of every chunked pass on masked-off steps.  An
    explicit ``block_b`` is respected and only K is tuned."""
    if not seq_chunk:
        return (block_b or auto_block_b(
            B, D, H, itemsize, n_dir=n_dir, training=True,
            vmem_budget=vmem_budget, stash_itemsize=stash_itemsize)), 0
    budget = vmem_budget or DEFAULT_VMEM_BUDGET
    T = max(T, 1)
    fixed_k = seq_chunk > 0
    K = min(seq_chunk, T) if fixed_k else min(
        256, 1 << (T - 1).bit_length())
    bb = block_b or max(8, 1 << (max(B, 1) - 1).bit_length())

    def usage(bb, K):
        return _chunked_usage(bb, K, D, H, itemsize, n_dir, stash_itemsize)

    while usage(bb, K) > budget:
        if not fixed_k and K > 16:
            K //= 2
        elif block_b is None and bb > 8:
            bb //= 2
        else:
            break   # floor: the weights themselves overrun the budget
    while not fixed_k and K > 16 and (_round_up(T, K) - T) * 8 > T:
        K //= 2                        # bound the masked-padding waste
    if block_b is None and bb >= B:
        bb = max(8, _round_up(B, 8))   # single tile: sublane multiple only
    return bb, K


# ---------------------------------------------------------------------------
# time-major helpers
# ---------------------------------------------------------------------------

def _tm(a):
    """(B, T, ...) <-> (T, B, ...): the one transpose at the public
    boundary."""
    return jnp.swapaxes(a, 0, 1)


def _pad_batch(a, Bp):
    """Zero-pad the batch axis (axis 1) of a time-major array to Bp."""
    B = a.shape[1]
    if B == Bp:
        return a
    return jnp.pad(a, ((0, 0), (0, Bp - B)) + ((0, 0),) * (a.ndim - 2))


def _pad_time(a, Tp):
    T = a.shape[0]
    if T == Tp:
        return a
    return jnp.pad(a, ((0, Tp - T),) + ((0, 0),) * (a.ndim - 1))


def _len_col(lengths, Bp):
    """(B,) lengths -> the (Bp, 1) int32 column the masked kernels take
    (rows added by batch padding get length 0)."""
    lens = lengths.astype(jnp.int32)
    return jnp.pad(lens, (0, Bp - lens.shape[0]))[:, None]


def _row(b):
    """(4H,) bias -> (1, 4H): a 2-D block Mosaic tiles like the rest."""
    return b.reshape(1, -1)


def _stash_dtype(stash_dtype):
    return jnp.dtype(stash_dtype or "float32")


def _tile(x, n_dir: int, H: int, block_b, vmem_budget, *, training: bool,
          stash_itemsize: int = 4):
    """The single source of the (block_b, padded_B) pair for a
    time-major x (T, B, D).  The stashing forward and the backward
    wrapper both derive the tile through here with ``training=True`` and
    identical arguments, so the backward's grid covers exactly the rows
    the forward padded (``_run_bwd`` asserts the invariant)."""
    if block_b is not None and block_b < 0:
        raise ValueError(f"block_b must be positive or 0/None (auto), "
                         f"got {block_b}")
    _, B, D = x.shape
    bb = block_b or auto_block_b(B, D, H, jnp.dtype(x.dtype).itemsize,
                                 n_dir=n_dir, training=training,
                                 vmem_budget=vmem_budget,
                                 stash_itemsize=stash_itemsize)
    return bb, _round_up(B, bb)


# ---------------------------------------------------------------------------
# forward kernels (inference / training-with-stash, uni- or bidirectional)
# ---------------------------------------------------------------------------

def _cell_math(x_t, hx, c_prev, wx, wh, b):
    """The one LSTM cell step shared by every kernel body (single-layer
    forward, chunked-recompute backward phase 1, fused stack): gate order
    i|f|g|o, forget bias +1, f32 accumulation.  ``hx`` is the recurrent
    input already rounded to the matmul dtype; ``b`` is the (1, 4H) bias
    row.  Returns the post-activation gates and the updated (c, h).  Keep
    this the single source — drift between kernel bodies would silently
    break the bit-identity and grad-parity contracts rather than crash."""
    gates = (
        jax.lax.dot_general(x_t, wx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(hx, wh, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        + b
    )
    H = wh.shape[-1] // 4
    i = jax.nn.sigmoid(gates[:, 0 * H:1 * H])
    f = jax.nn.sigmoid(gates[:, 1 * H:2 * H] + 1.0)
    g = jnp.tanh(gates[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(gates[:, 3 * H:4 * H])
    c = f * c_prev + i * g
    return i, f, g, o, c, o * jnp.tanh(c)


def _make_fwd_kernel(n_dir: int, stash: bool, revs=None, chunk: int = 0):
    """Kernel body over refs laid out as:

    inputs:  x * n_dir, then (wx, wh, b) * n_dir, then lengths if masked
    outputs: y * n_dir, then (acts, cseq) * n_dir if ``stash``
             (with ``chunk`` > 0 the per-step (acts, cseq) pair becomes
             the per-chunk (h_bound, c_bound) entry-carry pair, written
             once per chunk on its first grid step)
    scratch: (h, c) * n_dir

    ``revs`` enables masking: it carries each direction's reverse flag so
    the body can recover the real time index of grid step t and freeze
    the (h, c) carry / zero the output on padded steps.
    """
    masked = revs is not None
    n_in = 4 * n_dir + (1 if masked else 0)
    n_out = n_dir * (3 if stash else 1)

    def kernel(*refs):
        x_refs = refs[:n_dir]
        w_refs = refs[n_dir:4 * n_dir]
        out_refs = refs[n_in:n_in + n_out]
        scr_refs = refs[n_in + n_out:]
        t = pl.program_id(1)
        if masked:
            lens = refs[4 * n_dir][...]                     # (bb, 1) int32
            T = pl.num_programs(1)

        for d in range(n_dir):
            wx_ref, wh_ref, b_ref = w_refs[3 * d:3 * d + 3]
            h_ref, c_ref = scr_refs[2 * d:2 * d + 2]

            @pl.when(t == 0)
            def _init(h_ref=h_ref, c_ref=c_ref):
                h_ref[...] = jnp.zeros_like(h_ref)
                c_ref[...] = jnp.zeros_like(c_ref)

            x = x_refs[d][...]
            h = h_ref[...]
            c_prev = c_ref[...]
            if stash and chunk:
                # stash the chunk-ENTRY carry on the chunk's first step;
                # the output block's index map (t // chunk) keeps it
                # resident for the remaining chunk-1 visits
                hb_ref = out_refs[n_dir + 2 * d]
                cb_ref = out_refs[n_dir + 2 * d + 1]

                @pl.when(t % chunk == 0)
                def _bound(hb_ref=hb_ref, cb_ref=cb_ref, h=h, c=c_prev):
                    hb_ref[...] = h.astype(hb_ref.dtype)
                    cb_ref[...] = c.astype(cb_ref.dtype)
            i, f, g, o, c, h_new = _cell_math(
                x, h.astype(x.dtype), c_prev, wx_ref[...], wh_ref[...],
                b_ref[...])
            if masked:
                time_idx = (T - 1 - t) if revs[d] else t
                vm = time_idx < lens                        # (bb, 1)
                c = jnp.where(vm, c, c_prev)                # freeze carry
                y = jnp.where(vm, h_new, jnp.zeros_like(h_new))
                h_new = jnp.where(vm, h_new, h)
            else:
                y = h_new
            c_ref[...] = c
            h_ref[...] = h_new
            out_refs[d][...] = y.astype(out_refs[d].dtype)
            if stash and not chunk:
                acts_ref = out_refs[n_dir + 2 * d]
                cseq_ref = out_refs[n_dir + 2 * d + 1]
                acts_ref[...] = jnp.concatenate(
                    [i, f, g, o], axis=-1).astype(acts_ref.dtype)
                cseq_ref[...] = c.astype(cseq_ref.dtype)

    return kernel


def _xmap(T: int, reverse: bool):
    if reverse:
        return lambda ib, t: (T - 1 - t, ib, 0)
    return lambda ib, t: (t, ib, 0)


def _const(ndim: int):
    """Index map of a block that stays resident for the whole grid."""
    return lambda *_: (0,) * ndim


def _run_fwd(ws, x, revs, *, stash: bool, block_b, vmem_budget, interpret,
             lengths=None, stash_dtype=None, seq_chunk: int = 0):
    """Run the forward kernel for one or two directions in one grid pass.

    x: time-major (T, B, D); ws: ((wx, wh, b), ...) per direction; revs:
    matching reverse flags.  ``lengths`` (B,) int32 selects the masked
    kernel (padded rows of the batch tile get length 0).  Returns
    (outs, bb): outs is the flat pallas output list over the *padded*
    batch, time-major (y per direction, then (acts, cseq) pairs if
    stash, in ``stash_dtype``).

    ``seq_chunk`` (resolved chunk length K > 0, stash only) switches the
    per-step residual stash to per-chunk (h_bound, c_bound) entry
    carries; the caller must have padded T to a multiple of K and passed
    ``lengths`` (the chunked path is always masked).
    """
    T, B, D = x.shape
    H = ws[0][1].shape[0]
    n_dir = len(ws)
    sdt = _stash_dtype(stash_dtype)
    if seq_chunk:
        assert stash and lengths is not None and T % seq_chunk == 0, \
            (stash, lengths is None, T, seq_chunk)
    bb, Bp = _tile(x, n_dir, H, block_b, vmem_budget, training=stash,
                   stash_itemsize=sdt.itemsize)
    xp = _pad_batch(x, Bp)
    grid = (Bp // bb, T)

    operands, in_specs = [], []
    for rev in revs:
        operands.append(xp)
        in_specs.append(pl.BlockSpec((None, bb, D), _xmap(T, rev)))
    for wx, wh, b in ws:
        operands += [wx, wh, _row(b)]
        in_specs += [pl.BlockSpec((D, 4 * H), _const(2)),
                     pl.BlockSpec((H, 4 * H), _const(2)),
                     pl.BlockSpec((1, 4 * H), _const(2))]
    if lengths is not None:
        operands.append(_len_col(lengths, Bp))
        in_specs.append(pl.BlockSpec((bb, 1), lambda ib, t: (ib, 0)))

    out_specs = [pl.BlockSpec((None, bb, H), _xmap(T, rev)) for rev in revs]
    out_shape = [jax.ShapeDtypeStruct((T, Bp, H), x.dtype) for _ in revs]
    if stash and seq_chunk:
        K = seq_chunk
        for _ in revs:
            # chunk-entry (h, c) carries; grid step t writes chunk t // K
            out_specs += [pl.BlockSpec((None, bb, H),
                                       lambda ib, t: (t // K, ib, 0))] * 2
            out_shape += [jax.ShapeDtypeStruct((T // K, Bp, H), sdt)] * 2
    elif stash:
        for rev in revs:
            out_specs += [pl.BlockSpec((None, bb, 4 * H), _xmap(T, rev)),
                          pl.BlockSpec((None, bb, H), _xmap(T, rev))]
            out_shape += [jax.ShapeDtypeStruct((T, Bp, 4 * H), sdt),
                          jax.ShapeDtypeStruct((T, Bp, H), sdt)]

    scratch = []
    for _ in revs:
        scratch += [pltpu.VMEM((bb, H), jnp.float32),
                    pltpu.VMEM((bb, H), jnp.float32)]

    outs = pl.pallas_call(
        _make_fwd_kernel(n_dir, stash,
                         revs if lengths is not None else None,
                         chunk=seq_chunk),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(vmem_budget),
        interpret=_resolve_interpret(interpret),
        name="blstm_fwd" if n_dir == 2 else "lstm_fwd",
    )(*operands)
    return list(outs), bb


# ---------------------------------------------------------------------------
# backward kernel (one direction; the BLSTM VJP runs it once per direction)
# ---------------------------------------------------------------------------

def _make_bwd_kernel(reverse: bool, masked: bool):
    """One reverse-recurrence step.  Grid (B//bB, T); grid axis 1 walks
    the recurrence backwards (index maps reverse time), carrying (dh, dc)
    in scratch and accumulating dWx/dWh/db into constant-mapped f32
    output blocks that stay VMEM-resident for the whole grid.

    ``masked`` adds a trailing lengths input: on padded steps dgates are
    zeroed (so dx and the dW accumulators see nothing) and the (dh, dc)
    carries pass through unchanged — the exact VJP of the frozen-carry
    forward.  ``reverse`` is only consulted when masked (to recover the
    real time index of grid step r)."""

    def kernel(*refs):
        (dy_ref, acts_ref, c_ref, cprev_ref, hprev_ref, x_ref,
         wx_ref, wh_ref) = refs[:8]
        len_ref = refs[8] if masked else None
        (dx_ref, dwx_ref, dwh_ref, db_ref,
         dh_ref, dc_ref) = refs[8 + (1 if masked else 0):]
        ib = pl.program_id(0)
        r = pl.program_id(1)

        @pl.when(r == 0)
        def _init_carry():
            dh_ref[...] = jnp.zeros_like(dh_ref)
            dc_ref[...] = jnp.zeros_like(dc_ref)

        @pl.when((r == 0) & (ib == 0))
        def _init_accum():
            dwx_ref[...] = jnp.zeros_like(dwx_ref)
            dwh_ref[...] = jnp.zeros_like(dwh_ref)
            db_ref[...] = jnp.zeros_like(db_ref)

        # the last grid step is the *first* step of the original
        # recurrence: its h_{t-1}/c_{t-1} are the zero initial state,
        # not array values
        boundary = r == pl.num_programs(1) - 1
        H = dh_ref.shape[-1]
        acts = acts_ref[...].astype(jnp.float32)
        i = acts[:, 0 * H:1 * H]
        f = acts[:, 1 * H:2 * H]
        g = acts[:, 2 * H:3 * H]
        o = acts[:, 3 * H:4 * H]
        c = c_ref[...].astype(jnp.float32)
        zero = jnp.zeros_like(c)
        c_prev = jnp.where(boundary, zero,
                           cprev_ref[...].astype(jnp.float32))
        h_prev = jnp.where(boundary, zero,
                           hprev_ref[...].astype(jnp.float32))

        dh_carry = dh_ref[...]
        dc_carry = dc_ref[...]
        dh = dy_ref[...].astype(jnp.float32) + dh_carry
        tc = jnp.tanh(c)
        dc = dh * o * (1.0 - tc * tc) + dc_carry
        if masked:
            T = pl.num_programs(1)
            time_idx = r if reverse else T - 1 - r
            vm = time_idx < len_ref[...]                    # (bb, 1)
            dh = jnp.where(vm, dh, zero)
            dc = jnp.where(vm, dc, zero)
        dgates = jnp.concatenate([
            dc * g * i * (1.0 - i),          # d pre-act input gate
            dc * c_prev * f * (1.0 - f),     # d pre-act forget gate
            dc * i * (1.0 - g * g),          # d pre-act cell candidate
            dh * tc * o * (1.0 - o),         # d pre-act output gate
        ], axis=-1)

        wx = wx_ref[...].astype(jnp.float32)
        wh = wh_ref[...].astype(jnp.float32)
        dx_ref[...] = jax.lax.dot_general(
            dgates, wx, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dx_ref.dtype)
        dh_new = jax.lax.dot_general(
            dgates, wh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dc_new = dc * f
        if masked:
            # padded step: h_t = h_{t-1}, c_t = c_{t-1} — the carries
            # pass straight through
            dh_new = jnp.where(vm, dh_new, dh_carry)
            dc_new = jnp.where(vm, dc_new, dc_carry)
        dh_ref[...] = dh_new
        dc_ref[...] = dc_new

        x = x_ref[...].astype(jnp.float32)
        dwx_ref[...] += jax.lax.dot_general(
            x, dgates, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dwh_ref[...] += jax.lax.dot_general(
            h_prev, dgates, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        db_ref[...] += jnp.sum(dgates, axis=0, keepdims=True)

    return kernel


def _bwd_tmap(T: int, reverse: bool):
    """Time index of the step grid position r processes (reverse
    recurrence order: the forward direction walks T-1..0)."""
    if reverse:
        return lambda ib, r: (r, ib, 0)
    return lambda ib, r: (T - 1 - r, ib, 0)


def _bwd_pmap(T: int, reverse: bool):
    """Time index of the *previous* recurrence step (clamped at the
    boundary; the kernel zeroes the value there)."""
    if reverse:
        return lambda ib, r: (jnp.minimum(r + 1, T - 1), ib, 0)
    return lambda ib, r: (jnp.maximum(T - 2 - r, 0), ib, 0)


def _grad_outs(D: int, H: int):
    """Specs and shapes of the constant-mapped f32 dWx/dWh/db blocks."""
    specs = [pl.BlockSpec((D, 4 * H), _const(2)),
             pl.BlockSpec((H, 4 * H), _const(2)),
             pl.BlockSpec((1, 4 * H), _const(2))]
    shapes = [jax.ShapeDtypeStruct((D, 4 * H), jnp.float32),
              jax.ShapeDtypeStruct((H, 4 * H), jnp.float32),
              jax.ShapeDtypeStruct((1, 4 * H), jnp.float32)]
    return specs, shapes


def _run_bwd(wx, wh, xp, yp, acts, cseq, dyp, *, reverse: bool, bb: int,
             vmem_budget, interpret, lens_p=None):
    """Backward kernel over padded time-major arrays -> (dxp, dwx, dwh,
    db), f32 weight grads (caller casts to param dtypes; db is (1, 4H)).
    ``lens_p`` is the (Bp, 1) lengths column for the masked VJP (None =
    dense)."""
    T, Bp, D = xp.shape
    H = wh.shape[0]
    assert Bp % bb == 0, (Bp, bb)   # forward/backward tile lockstep
    tmap = _bwd_tmap(T, reverse)
    pmap = _bwd_pmap(T, reverse)
    masked = lens_p is not None

    in_specs = [
        pl.BlockSpec((None, bb, H), tmap),          # dy_t
        pl.BlockSpec((None, bb, 4 * H), tmap),      # stashed gates_t
        pl.BlockSpec((None, bb, H), tmap),          # c_t
        pl.BlockSpec((None, bb, H), pmap),          # c_{t-1}
        pl.BlockSpec((None, bb, H), pmap),          # h_{t-1} (= y)
        pl.BlockSpec((None, bb, D), tmap),          # x_t
        pl.BlockSpec((D, 4 * H), _const(2)),
        pl.BlockSpec((H, 4 * H), _const(2)),
    ]
    operands = [dyp, acts, cseq, cseq, yp, xp, wx, wh]
    if masked:
        in_specs.append(pl.BlockSpec((bb, 1), lambda ib, r: (ib, 0)))
        operands.append(lens_p)
    g_specs, g_shapes = _grad_outs(D, H)

    return pl.pallas_call(
        _make_bwd_kernel(reverse, masked),
        grid=(Bp // bb, T),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((None, bb, D), tmap)] + g_specs,
        out_shape=[jax.ShapeDtypeStruct((T, Bp, D), xp.dtype)] + g_shapes,
        scratch_shapes=[
            pltpu.VMEM((bb, H), jnp.float32),
            pltpu.VMEM((bb, H), jnp.float32),
        ],
        compiler_params=_compiler_params(vmem_budget),
        interpret=_resolve_interpret(interpret),
        name="lstm_bwd",
    )(*operands)


# ---------------------------------------------------------------------------
# chunked-recompute backward (one direction; grid walks chunks in reverse)
# ---------------------------------------------------------------------------

def _make_bwd_chunked_kernel(reverse: bool, K: int):
    """One grid step = one K-frame chunk, processed in reverse recurrence
    order (grid axis 1 index maps reverse the chunk axis).  Phase 1
    re-runs the forward for the chunk from its stashed entry carry,
    rebuilding the gate/cell residuals in VMEM scratch; phase 2 runs the
    K reverse-recurrence steps against them, carrying (dh, dc) across
    chunks in scratch and accumulating dWx/dWh/db into constant-mapped
    f32 output blocks.  Chunk blocks are time-major (K, bB, ·), so a
    frame is a dynamic index on the leading, untiled axis.  Always
    masked — the chunked wrapper synthesizes ``lengths`` (= T) for dense
    inputs so time padding to a K multiple stays exact."""

    def kernel(dy_ref, x_ref, hb_ref, cb_ref, wx_ref, wh_ref, b_ref,
               len_ref, dx_ref, dwx_ref, dwh_ref, db_ref,
               g_scr, hp_scr, cp_scr, dh_ref, dc_ref):
        ib = pl.program_id(0)
        r = pl.program_id(1)
        n = pl.num_programs(1)
        H = dh_ref.shape[-1]

        @pl.when(r == 0)
        def _init_carry():
            dh_ref[...] = jnp.zeros_like(dh_ref)
            dc_ref[...] = jnp.zeros_like(dc_ref)

        @pl.when((r == 0) & (ib == 0))
        def _init_accum():
            dwx_ref[...] = jnp.zeros_like(dwx_ref)
            dwh_ref[...] = jnp.zeros_like(dwh_ref)
            db_ref[...] = jnp.zeros_like(db_ref)

        # real-time base of this grid step's x/dy/dx blocks (= block
        # index * K; the recurrence chunk is n-1-r in both directions)
        base = (r if reverse else n - 1 - r) * K
        lens = len_ref[...]                                 # (bb, 1)
        b = b_ref[...]
        xdt = x_ref.dtype
        zero = jnp.zeros((dh_ref.shape[0], H), jnp.float32)

        def _vm(lt):
            return (base + lt) < lens

        # ---- phase 1: recompute the chunk's forward in VMEM ----------
        # u walks the chunk in recurrence order; lt is the real-time
        # position inside the block (the reverse direction's recurrence
        # walks real time descending)
        def fwd_body(u, hc):
            h, c = hc
            lt = (K - 1 - u) if reverse else u
            x_t = x_ref[lt]
            hx = h.astype(xdt)
            hp_scr[lt] = hx.astype(jnp.float32)
            cp_scr[lt] = c
            i, f, g, o, c_new, h_new = _cell_math(
                x_t, hx, c, wx_ref[...], wh_ref[...], b)
            g_scr[lt] = jnp.concatenate([i, f, g, o], axis=-1)
            vm = _vm(lt)
            return (jnp.where(vm, h_new, h), jnp.where(vm, c_new, c))

        h0 = hb_ref[...].astype(jnp.float32)
        c0 = cb_ref[...].astype(jnp.float32)
        jax.lax.fori_loop(0, K, fwd_body, (h0, c0))

        # ---- phase 2: reverse-recurrence backward over the chunk -----
        wx = wx_ref[...].astype(jnp.float32)
        wh = wh_ref[...].astype(jnp.float32)

        def bwd_body(u, carry):
            dh_c, dc_c = carry
            s = K - 1 - u                       # recurrence-local step
            lt = (K - 1 - s) if reverse else s
            acts = g_scr[lt]
            i = acts[:, 0 * H:1 * H]
            f = acts[:, 1 * H:2 * H]
            g = acts[:, 2 * H:3 * H]
            o = acts[:, 3 * H:4 * H]
            c_prev = cp_scr[lt]
            vm = _vm(lt)
            c = jnp.where(vm, f * c_prev + i * g, c_prev)
            dh = dy_ref[lt].astype(jnp.float32) + dh_c
            tc = jnp.tanh(c)
            dc = dh * o * (1.0 - tc * tc) + dc_c
            dh = jnp.where(vm, dh, zero)
            dc = jnp.where(vm, dc, zero)
            dgates = jnp.concatenate([
                dc * g * i * (1.0 - i),
                dc * c_prev * f * (1.0 - f),
                dc * i * (1.0 - g * g),
                dh * tc * o * (1.0 - o),
            ], axis=-1)
            dx_ref[lt] = jax.lax.dot_general(
                dgates, wx, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dx_ref.dtype)
            dh_new = jax.lax.dot_general(
                dgates, wh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dc_new = dc * f
            x_t = x_ref[lt].astype(jnp.float32)
            h_prev = hp_scr[lt]
            dwx_ref[...] += jax.lax.dot_general(
                x_t, dgates, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dwh_ref[...] += jax.lax.dot_general(
                h_prev, dgates, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            db_ref[...] += jnp.sum(dgates, axis=0, keepdims=True)
            return (jnp.where(vm, dh_new, dh_c),
                    jnp.where(vm, dc_new, dc_c))

        dh_c, dc_c = jax.lax.fori_loop(
            0, K, bwd_body, (dh_ref[...], dc_ref[...]))
        dh_ref[...] = dh_c
        dc_ref[...] = dc_c

    return kernel


def _run_bwd_chunked(wx, wh, b, xp, hbound, cbound, dyp, lens_p, *,
                     reverse: bool, bb: int, vmem_budget, interpret):
    """Chunked backward over padded time-major arrays -> (dxp, dwx, dwh,
    db), f32 weight grads.  ``xp``/``dyp`` are row- and time-padded (T
    multiple of the chunk length); ``hbound``/``cbound`` are the
    (n_chunks, Bp, H) chunk-entry carries of the chunk-stash forward;
    ``lens_p`` the (Bp, 1) lengths column (always present on the chunked
    path)."""
    T, Bp, D = xp.shape
    H = wh.shape[0]
    n = hbound.shape[0]
    K = T // n
    assert Bp % bb == 0 and T % n == 0, (Bp, bb, T, n)

    def cmap(ib, r):              # x/dy/dx chunk block, real-time order
        return (r, ib, 0) if reverse else (n - 1 - r, ib, 0)

    def bmap(ib, r):              # entry carries, recurrence-chunk order
        return (n - 1 - r, ib, 0)

    g_specs, g_shapes = _grad_outs(D, H)
    return pl.pallas_call(
        _make_bwd_chunked_kernel(reverse, K),
        grid=(Bp // bb, n),
        in_specs=[
            pl.BlockSpec((K, bb, H), cmap),           # dy chunk
            pl.BlockSpec((K, bb, D), cmap),           # x chunk
            pl.BlockSpec((None, bb, H), bmap),        # h entry carry
            pl.BlockSpec((None, bb, H), bmap),        # c entry carry
            pl.BlockSpec((D, 4 * H), _const(2)),
            pl.BlockSpec((H, 4 * H), _const(2)),
            pl.BlockSpec((1, 4 * H), _const(2)),
            pl.BlockSpec((bb, 1), lambda ib, r: (ib, 0)),
        ],
        out_specs=[pl.BlockSpec((K, bb, D), cmap)] + g_specs,
        out_shape=[jax.ShapeDtypeStruct((T, Bp, D), xp.dtype)] + g_shapes,
        scratch_shapes=[
            pltpu.VMEM((K, bb, 4 * H), jnp.float32),   # gate residuals
            pltpu.VMEM((K, bb, H), jnp.float32),       # h_{t-1} (rounded)
            pltpu.VMEM((K, bb, H), jnp.float32),       # c_{t-1}
            pltpu.VMEM((bb, H), jnp.float32),          # dh carry
            pltpu.VMEM((bb, H), jnp.float32),          # dc carry
        ],
        compiler_params=_compiler_params(vmem_budget),
        interpret=_resolve_interpret(interpret),
        name="lstm_bwd_chunked",
    )(dyp, xp, hbound, cbound, wx, wh, _row(b), lens_p)


# ---------------------------------------------------------------------------
# custom-VJP wiring (time-major inside; the public wrappers transpose)
# ---------------------------------------------------------------------------

def _len_cotangent(lengths):
    """Cotangent for the integer lengths input (float0 per JAX's rule for
    non-differentiable primal dtypes; None when lengths wasn't passed)."""
    if lengths is None:
        return None
    return np.zeros(lengths.shape, jax.dtypes.float0)


def _run_fwd_train(ws, x, revs, lengths, *, interpret, block_b,
                   vmem_budget, stash_dtype, seq_chunk):
    """Stashing training forward shared by every custom-VJP fwd rule.

    x is time-major (T, B, D).  Returns (ys, res): ys are per-direction
    (T, B, H) outputs (trimmed), res the residual tuple
    :func:`_run_bwd_train` consumes.  On the chunked path (``seq_chunk``
    != 0) x is zero-padded to a chunk multiple of T, a full-T
    ``lengths`` is synthesized for dense inputs, and the residuals are
    the (h, c) chunk-entry carries instead of the per-step gate/cell
    stash."""
    T, B, D = x.shape
    H = ws[0][1].shape[0]
    n_dir = len(ws)
    sdt = _stash_dtype(stash_dtype)
    if seq_chunk:
        bb, K = auto_tile(B, T, D, H, jnp.dtype(x.dtype).itemsize,
                          n_dir=n_dir, vmem_budget=vmem_budget,
                          stash_itemsize=sdt.itemsize,
                          seq_chunk=seq_chunk, block_b=block_b)
        lens = (jnp.full((B,), T, jnp.int32) if lengths is None
                else jnp.minimum(lengths.astype(jnp.int32), T))
        outs, _ = _run_fwd(ws, _pad_time(x, _round_up(T, K)), revs,
                           stash=True, block_b=bb,
                           vmem_budget=vmem_budget, interpret=interpret,
                           lengths=lens, stash_dtype=stash_dtype,
                           seq_chunk=K)
        ys = [outs[d][:T, :B] for d in range(n_dir)]
        return ys, (x, lens, tuple(outs[n_dir:]))
    outs, _ = _run_fwd(ws, x, revs, stash=True, block_b=block_b,
                       vmem_budget=vmem_budget, interpret=interpret,
                       lengths=lengths, stash_dtype=stash_dtype)
    ys = [outs[d][:, :B] for d in range(n_dir)]
    return ys, (x, lengths, tuple(outs))


def _run_bwd_train(ws, res, dys, revs, *, interpret, block_b,
                   vmem_budget, stash_dtype, seq_chunk):
    """Backward shared by every custom-VJP bwd rule: one `_run_bwd` /
    `_run_bwd_chunked` call per direction against the residuals of
    :func:`_run_fwd_train`.  Returns (per-direction (dwx, dwh, db) f32,
    dx summed over directions, trimmed, time-major, f32)."""
    x, lengths, stash = res
    T, B, D = x.shape
    H = ws[0][1].shape[0]
    n_dir = len(ws)
    sdt = _stash_dtype(stash_dtype)
    grads, dx = [], 0
    if seq_chunk:
        bb, K = auto_tile(B, T, D, H, jnp.dtype(x.dtype).itemsize,
                          n_dir=n_dir, vmem_budget=vmem_budget,
                          stash_itemsize=sdt.itemsize,
                          seq_chunk=seq_chunk, block_b=block_b)
        Bp = stash[0].shape[1]
        assert Bp == _round_up(B, bb), (Bp, B, bb)
        Tp = _round_up(T, K)
        xp = _pad_batch(_pad_time(x, Tp), Bp)
        lp = _len_col(lengths, Bp)
        for d, ((wx, wh, b), rev) in enumerate(zip(ws, revs)):
            dyp = _pad_batch(_pad_time(dys[d], Tp), Bp)
            dxp, dwx, dwh, db = _run_bwd_chunked(
                wx, wh, b, xp, stash[2 * d], stash[2 * d + 1], dyp, lp,
                reverse=rev, bb=bb, vmem_budget=vmem_budget,
                interpret=interpret)
            grads.append((dwx, dwh, db[0]))
            dx = dx + dxp[:T, :B].astype(jnp.float32)
        return grads, dx
    bb, Bp = _tile(x, n_dir, H, block_b, vmem_budget, training=True,
                   stash_itemsize=sdt.itemsize)
    assert Bp == stash[0].shape[1], (Bp, stash[0].shape)
    xp = _pad_batch(x, Bp)
    lp = None if lengths is None else _len_col(lengths, Bp)
    for d, ((wx, wh, b), rev) in enumerate(zip(ws, revs)):
        yp = stash[d]
        acts, cseq = stash[n_dir + 2 * d], stash[n_dir + 2 * d + 1]
        dxp, dwx, dwh, db = _run_bwd(
            wx, wh, xp, yp, acts, cseq, _pad_batch(dys[d], Bp),
            reverse=rev, bb=bb, vmem_budget=vmem_budget,
            interpret=interpret, lens_p=lp)
        grads.append((dwx, dwh, db[0]))
        dx = dx + dxp[:, :B].astype(jnp.float32)
    return grads, dx


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lstm_vjp(static, wx, wh, b, x, lengths):
    reverse, interpret, block_b, vmem_budget = static[:4]
    outs, _ = _run_fwd(((wx, wh, b),), x, (reverse,), stash=False,
                       block_b=block_b, vmem_budget=vmem_budget,
                       interpret=interpret, lengths=lengths)
    return outs[0][:, :x.shape[1]]


def _lstm_vjp_fwd(static, wx, wh, b, x, lengths):
    reverse, interpret, block_b, vmem_budget, stash_dtype, seq_chunk = \
        static
    ys, res = _run_fwd_train(((wx, wh, b),), x, (reverse,), lengths,
                             interpret=interpret, block_b=block_b,
                             vmem_budget=vmem_budget,
                             stash_dtype=stash_dtype,
                             seq_chunk=seq_chunk)
    return ys[0], (wx, wh, b, lengths, res)


def _lstm_vjp_bwd(static, fullres, dy):
    reverse, interpret, block_b, vmem_budget, stash_dtype, seq_chunk = \
        static
    wx, wh, b, lengths, res = fullres
    grads, dx = _run_bwd_train(((wx, wh, b),), res, (dy,), (reverse,),
                               interpret=interpret, block_b=block_b,
                               vmem_budget=vmem_budget,
                               stash_dtype=stash_dtype,
                               seq_chunk=seq_chunk)
    (dwx, dwh, db), = grads
    return (dwx.astype(wx.dtype), dwh.astype(wh.dtype),
            db.astype(b.dtype), dx.astype(res[0].dtype),
            _len_cotangent(lengths))


_lstm_vjp.defvjp(_lstm_vjp_fwd, _lstm_vjp_bwd)


def lstm_sequence(wx, wh, b, x, lengths=None, *, reverse: bool = False,
                  interpret: bool = None, block_b: int = None,
                  vmem_budget: int = None, stash_dtype: str = None,
                  seq_chunk: int = 0):
    """x: (B, T, D) -> (B, T, H); weights wx (D,4H), wh (H,4H), b (4H,).

    Differentiable (custom VJP; see module docstring).  ``block_b``
    tiles the batch (None -> :func:`auto_block_b`).  ``lengths`` (B,)
    int selects the masked recurrence (frozen carry + zeroed output on
    padded steps); ``stash_dtype`` ('float32' | 'bfloat16') sets the
    training-forward residual stash precision; ``seq_chunk`` (K > 0
    frames, or -1 for auto) switches training to the sequence-chunked
    recompute backward (O(T/K) residual stash)."""
    return _tm(_lstm_vjp((bool(reverse), interpret, block_b, vmem_budget,
                          stash_dtype, seq_chunk or 0),
                         wx, wh, b, _tm(x), lengths))


# ---------------------------------------------------------------------------
# custom-VJP wiring: fused bidirectional
# ---------------------------------------------------------------------------

_BLSTM_REVS = (False, True)


def _blstm_fwd_infer(layer, x, lengths, *, interpret, block_b,
                     vmem_budget):
    """Inference forward of one fused bidirectional layer, time-major."""
    wxf, whf, bf, wxb, whb, bb_ = layer
    outs, _ = _run_fwd(((wxf, whf, bf), (wxb, whb, bb_)), x, _BLSTM_REVS,
                       stash=False, block_b=block_b,
                       vmem_budget=vmem_budget, interpret=interpret,
                       lengths=lengths)
    B = x.shape[1]
    return jnp.concatenate([outs[0][:, :B], outs[1][:, :B]], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _blstm_vjp(static, wxf, whf, bf, wxb, whb, bb_, x, lengths):
    interpret, block_b, vmem_budget = static[:3]
    return _blstm_fwd_infer((wxf, whf, bf, wxb, whb, bb_), x, lengths,
                            interpret=interpret, block_b=block_b,
                            vmem_budget=vmem_budget)


def _blstm_vjp_fwd(static, wxf, whf, bf, wxb, whb, bb_, x, lengths):
    interpret, block_b, vmem_budget, stash_dtype, seq_chunk = static
    ys, res = _run_fwd_train(((wxf, whf, bf), (wxb, whb, bb_)), x,
                             _BLSTM_REVS, lengths, interpret=interpret,
                             block_b=block_b, vmem_budget=vmem_budget,
                             stash_dtype=stash_dtype,
                             seq_chunk=seq_chunk)
    y = jnp.concatenate(ys, axis=-1)
    return y, (wxf, whf, bf, wxb, whb, bb_, lengths, res)


def _blstm_vjp_bwd(static, fullres, dy):
    interpret, block_b, vmem_budget, stash_dtype, seq_chunk = static
    wxf, whf, bf, wxb, whb, bb_, lengths, res = fullres
    H = whf.shape[0]
    grads, dx = _run_bwd_train(
        ((wxf, whf, bf), (wxb, whb, bb_)), res,
        (dy[..., :H], dy[..., H:]), _BLSTM_REVS, interpret=interpret,
        block_b=block_b, vmem_budget=vmem_budget,
        stash_dtype=stash_dtype, seq_chunk=seq_chunk)
    (dwxf, dwhf, dbf), (dwxb, dwhb, dbb) = grads
    return (dwxf.astype(wxf.dtype), dwhf.astype(whf.dtype),
            dbf.astype(bf.dtype), dwxb.astype(wxb.dtype),
            dwhb.astype(whb.dtype), dbb.astype(bb_.dtype),
            dx.astype(res[0].dtype), _len_cotangent(lengths))


_blstm_vjp.defvjp(_blstm_vjp_fwd, _blstm_vjp_bwd)


def blstm_sequence(wx_fwd, wh_fwd, b_fwd, wx_bwd, wh_bwd, b_bwd, x,
                   lengths=None, *, interpret: bool = None,
                   block_b: int = None, vmem_budget: int = None,
                   stash_dtype: str = None, seq_chunk: int = 0):
    """Fused bidirectional layer: x (B, T, D) -> (B, T, 2H) with the
    forward-direction output in [..., :H] and the time-reversed
    direction in [..., H:] — one kernel invocation, both weight sets
    resident, bit-identical to two :func:`lstm_sequence` calls.

    ``lengths`` (B,) int masks padded steps (the reverse direction then
    reverses within each row's valid span); ``stash_dtype`` sets the
    training-forward residual stash precision; ``seq_chunk`` (K > 0
    frames, or -1 for auto) selects the sequence-chunked recompute
    backward (O(T/K) residual stash)."""
    return _tm(_blstm_vjp((interpret, block_b, vmem_budget, stash_dtype,
                           seq_chunk or 0),
                          wx_fwd, wh_fwd, b_fwd, wx_bwd, wh_bwd, b_bwd,
                          _tm(x), lengths))


# ---------------------------------------------------------------------------
# fused multi-layer stack (inter-layer h stays VMEM-resident)
# ---------------------------------------------------------------------------

def _stack_usage(bb: int, T: int, D: int, H: int, itemsize: int) -> int:
    """Modelled VMEM resident set of the fused-stack kernel at batch tile
    bb: one layer's weights for both directions and the x/y blocks, all
    double-buffered, plus the two (T, bB, 2H) inter-layer ping-pong
    buffers, which dominate as T grows (docs/kernels.md)."""
    Dm = max(D, 2 * H)
    blocks = (2 * (Dm + H) * 4 * H * itemsize   # one layer, both dirs
              + 2 * 4 * H * _BIAS_BLOCK
              + 2 * bb * (Dm + H) * itemsize    # x/y blocks, both dirs
              + bb * _LEN_BLOCK)
    return (2 * blocks
            + 2 * T * bb * 2 * H * itemsize     # ping-pong buffers
            + 4 * bb * H * 4                    # (h, c) x 2 dirs
            + 2 * 3 * bb * 4 * H * 4)           # f32 gate temps


def auto_stack_block_b(B: int, T: int, D: int, H: int, itemsize: int,
                       vmem_budget: int = None) -> int:
    """Batch tile for the fused-stack kernel: the ping-pong buffers scale
    with T, so the tile shrinks as sequences grow (floor 8 rows; if even
    the floor overruns the budget, `blstm_stack_sequence` falls back to
    the per-layer loop instead of overcommitting VMEM)."""
    return _fit_block_b(
        B, lambda bb: _stack_usage(bb, T, D, H, itemsize),
        vmem_budget or DEFAULT_VMEM_BUDGET)


def _make_stack_kernel(L: int, T: int, Dm: int, H: int, masked: bool):
    """Whole-stack body on the (B//bB, L, T) grid (L and T sequential,
    T innermost).  Per-direction math is op-for-op `_make_fwd_kernel`
    (shared via `_cell_math`); the only new moving part is the layer
    input: layer 0 reads the x block (zero-padded to Dm in HBM — exact,
    appended zero terms do not change the dot), layer l>0 reads layer
    l-1's output from the VMEM ping-pong buffer at its direction's real
    time index (the x index maps collapse to a constant block for l > 0,
    so x stays resident instead of being re-fetched every step).
    Outputs are written only by the last layer."""

    def kernel(*refs):
        (xf_ref, xb_ref, wxs_ref, whs_ref, bs_ref) = refs[:5]
        len_ref = refs[5] if masked else None
        yf_ref, yb_ref = refs[5 + (1 if masked else 0):][:2]
        (ybuf0, ybuf1, h0_ref, c0_ref, h1_ref, c1_ref) = refs[-6:]
        l = pl.program_id(1)
        t = pl.program_id(2)
        even = l % 2 == 0
        if masked:
            lens = len_ref[...]                             # (bb, 1)

        for d in range(2):
            x_ref = (xf_ref, xb_ref)[d]
            h_ref, c_ref = ((h0_ref, c0_ref), (h1_ref, c1_ref))[d]
            out_ref = (yf_ref, yb_ref)[d]
            tr = t if d == 0 else T - 1 - t       # real time this step

            @pl.when(t == 0)
            def _init(h_ref=h_ref, c_ref=c_ref):
                h_ref[...] = jnp.zeros_like(h_ref)
                c_ref[...] = jnp.zeros_like(c_ref)

            # layer input: x block for l == 0, else the previous layer's
            # buffer (ping-pong: even layers write ybuf0, odd ybuf1)
            x_in = x_ref[...]
            prev = jnp.where(even, ybuf1[tr], ybuf0[tr])
            if Dm > 2 * H:
                prev = jnp.pad(prev, ((0, 0), (0, Dm - 2 * H)))
            inp = jnp.where(l == 0, x_in, prev.astype(x_in.dtype))

            h = h_ref[...]
            c_prev = c_ref[...]
            i, f, g, o, c, h_new = _cell_math(
                inp, h.astype(inp.dtype), c_prev, wxs_ref[d],
                whs_ref[d], bs_ref[d])
            if masked:
                vm = tr < lens
                c = jnp.where(vm, c, c_prev)
                y = jnp.where(vm, h_new, jnp.zeros_like(h_new))
                h_new = jnp.where(vm, h_new, h)
            else:
                y = h_new
            c_ref[...] = c
            h_ref[...] = h_new
            yb_val = y.astype(ybuf0.dtype)

            @pl.when(even)
            def _w0(yb_val=yb_val, tr=tr, d=d):
                ybuf0[tr, :, d * H:(d + 1) * H] = yb_val

            @pl.when(jnp.logical_not(even))
            def _w1(yb_val=yb_val, tr=tr, d=d):
                ybuf1[tr, :, d * H:(d + 1) * H] = yb_val

            @pl.when(l == L - 1)
            def _out(out_ref=out_ref, y=y):
                out_ref[...] = y.astype(out_ref.dtype)

    return kernel


def _stack_layers(params):
    """Normalize the per-layer parameter pytree to a tuple of 6-tuples
    ((wxf, whf, bf, wxb, whb, bb), ...)."""
    return tuple(tuple(layer) for layer in params)


def _stack_primal(params, x, lengths, *, interpret, block_b, vmem_budget):
    """Inference stack over time-major x (T, B, D0) -> (T, B, 2H)."""
    layers = _stack_layers(params)
    L = len(layers)
    T, B, D0 = x.shape
    H = layers[0][1].shape[0]
    Dm = max(D0, 2 * H)
    itemsize = jnp.dtype(x.dtype).itemsize
    bb = block_b or auto_stack_block_b(B, T, D0, H, itemsize, vmem_budget)
    fused = not (block_b is None and _stack_usage(bb, T, D0, H, itemsize)
                 > (vmem_budget or DEFAULT_VMEM_BUDGET))
    print(f"blstm stack: {'fused' if fused else 'per-layer'} "
          f"(L={L}, T={T}, B={B})", flush=True)
    if not fused:
        # very long T: even the 8-row floor cannot hold the (T, bB, 2H)
        # ping-pong buffers — run the per-layer fused-BLSTM loop
        # (T-independent VMEM) instead of overcommitting/failing compile
        for layer in layers:
            x = _blstm_fwd_infer(layer, x, lengths, interpret=interpret,
                                 block_b=None, vmem_budget=vmem_budget)
        return x
    Bp = _round_up(B, bb)

    def padw(w):
        return jnp.pad(w, ((0, Dm - w.shape[0]), (0, 0)))

    wxs = jnp.stack([jnp.stack([padw(lw[0]), padw(lw[3])])
                     for lw in layers])                  # (L, 2, Dm, 4H)
    whs = jnp.stack([jnp.stack([lw[1], lw[4]]) for lw in layers])
    bs = jnp.stack([jnp.stack([_row(lw[2]), _row(lw[5])])
                    for lw in layers])                   # (L, 2, 1, 4H)
    xp = jnp.pad(x, ((0, 0), (0, Bp - B), (0, Dm - D0)))
    masked = lengths is not None

    # x is only consumed by layer 0; for l > 0 the maps collapse to a
    # constant block so it stays resident instead of re-streaming
    def xmap_f(ib, l, t):
        return (jnp.where(l == 0, t, 0), ib, 0)

    def xmap_b(ib, l, t):
        return (jnp.where(l == 0, T - 1 - t, 0), ib, 0)

    in_specs = [
        pl.BlockSpec((None, bb, Dm), xmap_f),
        pl.BlockSpec((None, bb, Dm), xmap_b),
        pl.BlockSpec((None, 2, Dm, 4 * H), lambda ib, l, t: (l, 0, 0, 0)),
        pl.BlockSpec((None, 2, H, 4 * H), lambda ib, l, t: (l, 0, 0, 0)),
        pl.BlockSpec((None, 2, 1, 4 * H), lambda ib, l, t: (l, 0, 0, 0)),
    ]
    operands = [xp, xp, wxs, whs, bs]
    if masked:
        in_specs.append(pl.BlockSpec((bb, 1), lambda ib, l, t: (ib, 0)))
        operands.append(_len_col(lengths, Bp))

    yf, yb = pl.pallas_call(
        _make_stack_kernel(L, T, Dm, H, masked),
        grid=(Bp // bb, L, T),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, bb, H), lambda ib, l, t: (t, ib, 0)),
            pl.BlockSpec((None, bb, H),
                         lambda ib, l, t: (T - 1 - t, ib, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((T, Bp, H), x.dtype)] * 2,
        scratch_shapes=[
            pltpu.VMEM((T, bb, 2 * H), x.dtype),    # ping-pong buffer 0
            pltpu.VMEM((T, bb, 2 * H), x.dtype),    # ping-pong buffer 1
            pltpu.VMEM((bb, H), jnp.float32),       # fwd-dir h
            pltpu.VMEM((bb, H), jnp.float32),       # fwd-dir c
            pltpu.VMEM((bb, H), jnp.float32),       # rev-dir h
            pltpu.VMEM((bb, H), jnp.float32),       # rev-dir c
        ],
        compiler_params=_compiler_params(vmem_budget),
        interpret=_resolve_interpret(interpret),
        name="blstm_stack",
    )(*operands)
    return jnp.concatenate([yf[:, :B], yb[:, :B]], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _stack_vjp(static, params, x, lengths):
    interpret, block_b, vmem_budget = static[:3]
    return _stack_primal(params, x, lengths, interpret=interpret,
                         block_b=block_b, vmem_budget=vmem_budget)


def _stack_vjp_fwd(static, params, x, lengths):
    interpret, block_b, vmem_budget, stash_dtype, seq_chunk = static
    layers = _stack_layers(params)
    xl, reses = x, []
    for li, (wxf, whf, bf, wxb, whb, bb_) in enumerate(layers):
        with jax.named_scope(f"blstm_l{li}"):
            ys, res = _run_fwd_train(((wxf, whf, bf), (wxb, whb, bb_)), xl,
                                     _BLSTM_REVS, lengths,
                                     interpret=interpret, block_b=block_b,
                                     vmem_budget=vmem_budget,
                                     stash_dtype=stash_dtype,
                                     seq_chunk=seq_chunk)
            reses.append(res)
            xl = jnp.concatenate(ys, axis=-1)
    return xl, (params, lengths, tuple(reses))


def _stack_vjp_bwd(static, fullres, dy):
    interpret, block_b, vmem_budget, stash_dtype, seq_chunk = static
    params, lengths, reses = fullres
    layers = _stack_layers(params)
    H = layers[0][1].shape[0]
    dparams = [None] * len(layers)
    for li in reversed(range(len(layers))):
        (wxf, whf, bf, wxb, whb, bb_) = layers[li]
        with jax.named_scope(f"blstm_l{li}"):
            grads, dx = _run_bwd_train(
                ((wxf, whf, bf), (wxb, whb, bb_)), reses[li],
                (dy[..., :H], dy[..., H:]), _BLSTM_REVS,
                interpret=interpret, block_b=block_b,
                vmem_budget=vmem_budget, stash_dtype=stash_dtype,
                seq_chunk=seq_chunk)
            (dwxf, dwhf, dbf), (dwxb, dwhb, dbb) = grads
            dparams[li] = (dwxf.astype(wxf.dtype), dwhf.astype(whf.dtype),
                           dbf.astype(bf.dtype), dwxb.astype(wxb.dtype),
                           dwhb.astype(whb.dtype), dbb.astype(bb_.dtype))
            dy = dx.astype(reses[li][0].dtype)   # next layer's cotangent
    return tuple(dparams), dy, _len_cotangent(lengths)


_stack_vjp.defvjp(_stack_vjp_fwd, _stack_vjp_bwd)


def blstm_stack_sequence(params, x, lengths=None, *,
                         interpret: bool = None, block_b: int = None,
                         vmem_budget: int = None, stash_dtype: str = None,
                         seq_chunk: int = 0):
    """The whole stacked BLSTM as one fused kernel: ``params`` is a
    sequence of per-layer ``(wx_fwd, wh_fwd, b_fwd, wx_bwd, wh_bwd,
    b_bwd)`` tuples (layer 0 consumes x's D features, deeper layers the
    previous layer's 2H); returns (B, T, 2H_last).

    The primal (inference) call keeps the inter-layer activations in
    VMEM — bit-identical to the per-layer :func:`blstm_sequence` loop —
    and prints which path it traced (``blstm stack: fused`` or, when
    even an 8-row tile's ping-pong buffers overrun the budget,
    ``blstm stack: per-layer``).  Under ``jax.vjp`` the custom rules run
    the per-layer stashing forwards/backwards (every layer's output is a
    residual the backward needs anyway), composing with ``lengths``,
    ``stash_dtype`` and ``seq_chunk`` exactly like the single-layer entry
    points."""
    return _tm(_stack_vjp((interpret, block_b, vmem_budget, stash_dtype,
                           seq_chunk or 0), _stack_layers(params), _tm(x),
                          lengths))
