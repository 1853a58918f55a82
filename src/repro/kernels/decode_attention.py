"""Pallas TPU decode-shaped attention — the serving hot path.

The per-token decode step is the inverse of the flash kernel's regime:
q is a single row per head while the KV cache is (B, S, KV, E) with S in
the thousands, so the step is HBM-bound on the cache read and the only
job of a kernel is to stream that read once at full bandwidth.  The
layout keeps the tiny (M, E) q block and the f32 online-softmax carry
(acc (M, E), m/l (M, 1)) resident in VMEM while the cache walks through
in ``block_s`` tiles on the inner sequential grid axis:

  grid = (B, KV, S // block_s);  VMEM per program:
      q (M, E), k/v tiles 2 * (block_s, E), out (M, E)
      + f32 scratch acc (M, E) + m, l (M, 1).

S-tile count never changes the resident set, so arbitrarily long caches
stream through a fixed VMEM budget (``auto_block_s_decode`` picks the
largest power-of-two tile that fits; ``decode_attn_vmem_bytes`` is the
single source of the accounting, quoted in docs/kernels.md).

GQA grouping mirrors ``repro.models.attention.attn_decode``: the H query
heads are reshaped to (KV, M = H // KV) groups so each grid point serves
one kv-head's M queries against one cache stripe — the cache tile is
read once for all M queries of its group.

Masking matches the jax reference exactly: position t is attended iff
``t <= pos`` (canonical) or ``t < pos`` (delta variant, old cache only)
and ``pos - t < window``.  Both ``pos`` and ``window`` are TRACED
scalars — the per-layer window rides through the layer scan as data
(models/attention.py module docstring) — so they enter the kernel as
(1, 1) SMEM blocks, never as static params.  Tiles entirely above
``pos`` are skipped; the ragged last tile is handled by masking scores
at ``t >= S`` AND zeroing out-of-bounds v rows (the block is padded with
garbage that may be non-finite, and 0 * nan = nan would otherwise leak
through the p @ v product).

The ``delta`` variant fuses ``attn_decode_delta``: the new token's K/V
column is folded into the online-softmax INIT (m = s_new, l = 1,
acc = v_new) before the cache streams through, so the concat-and-resoftmax
of the jax path disappears and the cache is still read exactly once.

Numerics: scores, softmax and the accumulator are f32 regardless of
cache dtype (matching the jax path's f32 softmax); the output is cast
back to q.dtype.  Parity with ``attn_decode``/``attn_decode_delta`` is
~1e-7 normalized in f32 (tests/test_decode_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lstm_cell import SCOPED_VMEM_BUDGET, _resolve_interpret

NEG_INF = -1e30
_NO_WINDOW = 2 ** 30  # window >= S is full attention (cf. GLOBAL_WINDOW)


def decode_attn_vmem_bytes(block_s: int, M: int, E: int,
                           itemsize: int = 4) -> int:
    """Resident VMEM bytes per grid program — independent of S."""
    qo = 2 * M * E * itemsize              # q block + out block
    kv = 2 * 2 * block_s * E * itemsize    # k + v tiles, double-buffered
    carry = (M * E + 2 * M) * 4            # f32 acc + m + l scratch
    return qo + kv + carry


def paged_attn_vmem_bytes(page_size: int, M: int, E: int, table_elems: int,
                          itemsize: int = 4) -> int:
    """Paged-mode resident bytes per grid program: the dense accounting
    at ``block_s = page_size`` plus the scalar-prefetched page table and
    (pos, window) meta in SMEM (``table_elems = B * table_width`` i32)."""
    return (decode_attn_vmem_bytes(page_size, M, E, itemsize)
            + 4 * (table_elems + 2))


def auto_block_s_decode(S: int, M: int, E: int, itemsize: int = 4,
                        vmem_budget=None, page_size: int = None) -> int:
    """Largest power-of-two S-tile (<= S, >= 8) within the VMEM budget.

    With ``page_size`` set (paged cache) the tile is PINNED to one page —
    the physical pages are not contiguous so a tile cannot span them —
    and this only validates that a page-sized tile fits the budget."""
    budget = vmem_budget or SCOPED_VMEM_BUDGET
    if page_size is not None:
        if decode_attn_vmem_bytes(page_size, M, E, itemsize) > budget:
            raise ValueError(
                f"page_size={page_size} tile exceeds the VMEM budget "
                f"({decode_attn_vmem_bytes(page_size, M, E, itemsize)} "
                f"> {budget}); shrink the page")
        return int(page_size)
    bs = min(512, 1 << max(int(S) - 1, 0).bit_length())
    while bs > 8 and decode_attn_vmem_bytes(bs, M, E, itemsize) > budget:
        bs //= 2
    return max(8, min(bs, S))


def _attend_tile(pos, win, q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, *, block_s, seq_len, n_tiles,
                 scale, delta, kn_ref=None, vn_ref=None):
    """One grid step of the online-softmax walk — shared verbatim by the
    dense and paged kernels (``pos``/``win`` arrive as traced scalars;
    only the BlockSpec index maps differ), so contiguous-page paged
    output is bit-exact vs dense at ``block_s == page_size``."""
    s_idx = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32)                       # (M, E)
    M, E = q.shape

    @pl.when(s_idx == 0)
    def _init():
        if delta:
            # fold the new-token column into the carry: p_new = 1 at init
            k1 = kn_ref[...].astype(jnp.float32)             # (1, E)
            v1 = vn_ref[...].astype(jnp.float32)
            s_new = jax.lax.dot_general(
                q, k1, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (M, 1)
            m_ref[...] = s_new
            l_ref[...] = jnp.ones((M, 1), jnp.float32)
            acc_ref[...] = jnp.broadcast_to(v1, (M, E))
        else:
            m_ref[...] = jnp.full((M, 1), NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros((M, 1), jnp.float32)
            acc_ref[...] = jnp.zeros((M, E), jnp.float32)

    @pl.when(s_idx * block_s <= pos)  # tiles above pos contribute nothing
    def _tile():
        k = k_ref[...].astype(jnp.float32)                   # (block_s, E)
        v = v_ref[...].astype(jnp.float32)
        t = s_idx * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        # ragged tail: garbage rows may be non-finite and 0 * nan = nan,
        # so v must be zeroed — masking the scores alone is not enough
        v = jnp.where(t.reshape(block_s, 1) < seq_len, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (M, block_s)
        ok = (t < pos + (0 if delta else 1)) & (pos - t < win) \
            & (t < seq_len)
        s = jnp.where(ok, s, NEG_INF)
        m_i, l_i, acc = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)
        l_ref[...] = alpha * l_i + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s_idx == n_tiles - 1)
    def _flush():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _decode_kernel(pos_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, block_s, seq_len, n_tiles,
                   scale, delta, kn_ref=None, vn_ref=None):
    _attend_tile(pos_ref[0, 0], win_ref[0, 0], q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, block_s=block_s, seq_len=seq_len,
                 n_tiles=n_tiles, scale=scale, delta=delta,
                 kn_ref=kn_ref, vn_ref=vn_ref)


def decode_attention(q, k_cache, v_cache, pos, *, window=None, k_new=None,
                     v_new=None, block_s=None, vmem_budget=None,
                     interpret=None):
    """Pallas decode attention.  q (B, 1, H, E) vs cache (B, S, KV, E).

    ``k_new``/``v_new`` None selects the canonical mask (t <= pos; cache
    already holds the new token — ``attn_decode``); passing both (B, 1,
    KV, E) selects the fused delta variant (old cache strictly t < pos
    plus the new column — ``attn_decode_delta``).  ``pos`` and ``window``
    may be traced scalars; window None means full attention.
    """
    B, _, H, E = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    M = H // KV
    delta = k_new is not None
    interpret = _resolve_interpret(interpret)
    if block_s is None:
        block_s = auto_block_s_decode(S, M, E, k_cache.dtype.itemsize,
                                      vmem_budget)
    block_s = max(1, min(block_s, S))
    n_tiles = pl.cdiv(S, block_s)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1, 1)
    win_arr = jnp.asarray(_NO_WINDOW if window is None else window,
                          jnp.int32).reshape(1, 1)
    qg = q.reshape(B, KV, M, E)
    smem = pl.BlockSpec((1, 1), lambda b, g, s: (0, 0),
                        memory_space=pltpu.SMEM)
    cache_spec = pl.BlockSpec((None, block_s, None, E),
                              lambda b, g, s: (b, s, g, 0))
    q_spec = pl.BlockSpec((None, None, M, E), lambda b, g, s: (b, g, 0, 0))
    in_specs = [smem, smem, q_spec, cache_spec, cache_spec]
    args = [pos_arr, win_arr, qg, k_cache, v_cache]
    kern = functools.partial(
        _decode_kernel, block_s=block_s, seq_len=S, n_tiles=n_tiles,
        scale=float(1.0 / np.sqrt(E)), delta=delta)
    if delta:
        new_spec = pl.BlockSpec((None, 1, None, E),
                                lambda b, g, s: (b, 0, g, 0))
        in_specs += [new_spec, new_spec]
        args += [k_new, v_new]

        def body(pos_ref, win_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref,
                 o_ref, acc_ref, m_ref, l_ref):
            kern(pos_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, kn_ref=kn_ref, vn_ref=vn_ref)
    else:
        body = kern
    out = pl.pallas_call(
        body,
        grid=(B, KV, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, M, E),
                               lambda b, g, s: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, M, E), q.dtype),
        scratch_shapes=[pltpu.VMEM((M, E), jnp.float32),
                        pltpu.VMEM((M, 1), jnp.float32),
                        pltpu.VMEM((M, 1), jnp.float32)],
        interpret=interpret,
    )(*args)
    return out.reshape(B, 1, H, E)


def paged_decode_attention(q, k_pages, v_pages, page_table, pos, *,
                           window=None, k_new=None, v_new=None,
                           vmem_budget=None, interpret=None):
    """Paged decode attention: q (B, 1, H, E) vs a page pool
    (n_pages, P, KV, E) walked through ``page_table`` (B, W) i32.

    The grid's inner axis is the LOGICAL page index s; the page table is
    scalar-prefetched (SMEM) so the k/v BlockSpec index maps resolve
    ``table[b, s]`` to a physical page before the DMA issues — the tile
    is pinned to one page (``block_s = P``), everything else (online-
    softmax carry, GQA grouping, windowing, the fused ``k_new``/``v_new``
    delta init, masking at ``t <= pos`` with t = s·P + i) is the dense
    kernel's ``_attend_tile`` unchanged.  Table rows may be padded with
    any valid physical page id beyond the request's allocated pages —
    those tiles start above ``pos`` and are skipped.
    """
    B, _, H, E = q.shape
    n_pages, P, KV = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    W = page_table.shape[-1]
    M = H // KV
    S = W * P                               # logical sequence length
    delta = k_new is not None
    interpret = _resolve_interpret(interpret)
    auto_block_s_decode(S, M, E, k_pages.dtype.itemsize, vmem_budget,
                        page_size=P)        # budget check only
    meta = jnp.stack([jnp.asarray(pos, jnp.int32).reshape(()),
                      jnp.asarray(_NO_WINDOW if window is None else window,
                                  jnp.int32).reshape(())])
    tbl = jnp.asarray(page_table, jnp.int32).reshape(B, W)
    qg = q.reshape(B, KV, M, E)
    page_spec = pl.BlockSpec((None, P, None, E),
                             lambda b, g, s, meta_ref, tbl_ref:
                             (tbl_ref[b, s], 0, g, 0))
    q_spec = pl.BlockSpec((None, None, M, E),
                          lambda b, g, s, meta_ref, tbl_ref: (b, g, 0, 0))
    in_specs = [q_spec, page_spec, page_spec]
    args = [qg, k_pages, v_pages]
    kern = functools.partial(
        _paged_kernel, seq_len=S, n_tiles=W,
        scale=float(1.0 / np.sqrt(E)), delta=delta)
    if delta:
        new_spec = pl.BlockSpec((None, 1, None, E),
                                lambda b, g, s, meta_ref, tbl_ref:
                                (b, 0, g, 0))
        in_specs += [new_spec, new_spec]
        args += [k_new, v_new]

        def body(meta_ref, tbl_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref,
                 o_ref, acc_ref, m_ref, l_ref):
            kern(meta_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, kn_ref=kn_ref, vn_ref=vn_ref)
    else:
        body = kern
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, W),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, M, E),
                               lambda b, g, s, meta_ref, tbl_ref:
                               (b, g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((M, E), jnp.float32),
                        pltpu.VMEM((M, 1), jnp.float32),
                        pltpu.VMEM((M, 1), jnp.float32)])
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, M, E), q.dtype),
        interpret=interpret,
    )(meta, tbl, *args)
    return out.reshape(B, 1, H, E)


def _paged_kernel(meta_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, seq_len, n_tiles, scale,
                  delta, kn_ref=None, vn_ref=None):
    # tbl_ref is consumed by the BlockSpec index maps; the tile math
    # sees logical positions only.
    block_s = k_ref.shape[0]                # one page per tile
    _attend_tile(meta_ref[0], meta_ref[1], q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, block_s=block_s, seq_len=seq_len,
                 n_tiles=n_tiles, scale=scale, delta=delta,
                 kn_ref=kn_ref, vn_ref=vn_ref)
