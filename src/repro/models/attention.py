"""Grouped-query attention with three execution paths:

* ``attn_seq``    — full-sequence (train / prefill / cross-attention).
  It takes the trainable Pallas kernel of ``repro.kernels.flash_attention``
  (``attn_fwd`` / ``attn_bwd``, the scores of a query block never leave
  VMEM) when :func:`takes_kernel` holds: on a TPU, with no window, no
  sequence sharding and bf16 operands whose heads the kernel tiles
  (whisper's encoder and decoder).  Otherwise it runs the XLA path: a
  q-chunked softmax whose f32 score block per chunk is recomputed in the
  backward by a per-chunk checkpoint, with causal and sliding-window
  masks applied per chunk (the CPU, the transformer family's traced
  per-layer windows, sequence-parallel layouts).
* ``attn_decode`` — single-token step against a KV cache (serve path).
  ``impl='pallas'`` selects the decode-shaped streaming kernel in
  ``repro.kernels.decode_attention`` (same masks, online softmax over
  S-tiles); ``attn_decode_delta(impl='pallas')`` uses its fused variant
  that folds the new-token column in without re-reading the cache.
* cross-attention (encoder-decoder) reuses ``attn_seq`` without a mask.

Sliding windows are mask-based: the per-layer window rides through the
layer ``scan`` as data, which lets heterogeneous stacks (hymba/llama4
global+local layers) share one compiled body.  See DESIGN.md §Attention.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro import obs
from repro.kernels import flash_attention as FA
from repro.models.common import apply_rope
from repro.sharding import ParamSpec

NEG_INF = -1e30
# checkpoint names of attn_seq's output and of the kernel's log-sum-exp,
# for a caller's remat policy (repro.models.encdec.LAYER_POLICY)
OUT_NAME, LSE_NAME = FA.OUT_NAME, FA.LSE_NAME


def _constrain_dims(x, assignments):
    """Constrain selected dims of x to mesh axes, leaving the others
    UNCONSTRAINED (a bare None would force replication — which silently
    un-shards a data-sharded batch dim, §Perf pair-C iter 3).  Entries with
    axes missing from the mesh are dropped (tests/examples run meshless).
    assignments: {dim: axis_or_None}; None means force-replicate that dim."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    spec = [P.UNCONSTRAINED] * x.ndim
    any_set = False
    for dim, axis in assignments.items():
        if axis is None:
            spec[dim] = None
            any_set = True
        elif axis in mesh.axis_names:
            spec[dim] = axis
            any_set = True
    if not any_set or not mesh.axis_names:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _gather_last(x):
    """Force the last dim (head_dim) to full size, other dims untouched."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    spec = [P.UNCONSTRAINED] * (x.ndim - 1) + [None]
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_param_specs(cfg, *, dtype=None):
    dt = dtype or cfg.param_dtype
    d, H, KV, E = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": ParamSpec((d, H, E), dt, ("embed", "heads", "head_dim"), "lecun"),
        "wk": ParamSpec((d, KV, E), dt, ("embed", "kv_heads", "head_dim"), "lecun"),
        "wv": ParamSpec((d, KV, E), dt, ("embed", "kv_heads", "head_dim"), "lecun"),
        "wo": ParamSpec((H, E, d), dt, ("heads", "head_dim", "embed"), "lecun"),
    }
    if cfg.use_bias:
        # whisper's projections: q, v and out carry a bias, k none
        p["bq"] = ParamSpec((H, E), "float32", ("heads", "head_dim"), "zeros")
        p["bv"] = ParamSpec((KV, E), "float32", ("kv_heads", "head_dim"), "zeros")
        p["bo"] = ParamSpec((d,), "float32", ("embed",), "zeros")
    return p


def qkv_project(cfg, p, xq, xkv, positions_q=None, positions_kv=None):
    q = jnp.einsum("bsd,dhe->bshe", xq, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", xkv, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", xkv, p["wv"])
    if "bq" in p:
        q = (q.astype(jnp.float32) + p["bq"]).astype(q.dtype)
        v = (v.astype(jnp.float32) + p["bv"]).astype(v.dtype)
    if positions_q is not None:
        q = apply_rope(q, positions_q, cfg.rope_theta)
    if positions_kv is not None:
        k = apply_rope(k, positions_kv, cfg.rope_theta)
    return q, k, v


def out_project(p, o):
    y = jnp.einsum("bshe,hed->bsd", o, p["wo"])
    if "bo" in p:
        y = (y.astype(jnp.float32) + p["bo"]).astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Full-sequence attention (train / prefill / cross)
# ---------------------------------------------------------------------------

def takes_kernel(backend: str, q, k, v, *, window, seq_shard) -> bool:
    """Whether :func:`attn_seq` runs the Pallas kernel: on a TPU, with no
    window and no sequence sharding, on bf16 q/k/v whose heads fill the
    kernel's 128-lane blocks (``flash_attention.supported``)."""
    return (backend == "tpu" and window is None and not seq_shard
            and q.dtype == k.dtype == v.dtype == jnp.bfloat16
            and FA.supported(q.shape[2], q.shape[3]))


def attn_seq(q, k, v, *, causal: bool, window=None, q_chunk: int = 512,
             seq_shard: bool = False, seq_shard_chunked: bool = False,
             batch_axis=""):
    """q: (B,Sq,H,E), k/v: (B,Sk,KV,E).  window: scalar (traced ok); a
    window >= Sk is full attention.  Returns (B,Sq,H,E), named
    :data:`OUT_NAME` for a remat policy.

    Where :func:`takes_kernel` holds (the backend it observes, the
    window, the sharding, the operands) the Pallas kernel computes it
    and its VJP recomputes the scores from the saved log-sum-exp
    (:data:`LSE_NAME`).  Otherwise the queries run in chunks of
    ``q_chunk`` positions, each chunk checkpointed so that the backward
    recomputes its scores; when Sq is not a multiple of it, the last
    chunk is shorter (whisper's 1500-frame encoder: two chunks of 512
    and one of 476).  Each trace counts once in the ``repro.obs``
    counter ``models/attn_seq``, tagged ``impl`` (``pallas``/``xla``),
    ``ragged`` (a shorter last chunk, or on the kernel a padded last
    query block) and ``causal``.

    seq_shard=True is the sequence-parallel mode (EXPERIMENTS.md §Perf):
    K/V are gathered to full head_dim (cheap: one (B,Sk,KV,E) gather per
    layer) and each q chunk's position dim is sharded over the 'model'
    axis, dividing attention FLOPs *and* score traffic by the model-axis
    size instead of replicating them."""
    B, Sq, H, E = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if takes_kernel(jax.default_backend(), q, k, v, window=window,
                    seq_shard=seq_shard):
        obs.counter("models/attn_seq", impl="pallas",
                    ragged=int(Sq % FA.block_rows(Sq) > 0),
                    causal=int(causal)).inc()
        return FA.flash_attention(q, k, v, causal=causal)
    G, M = KV, H // KV
    scale = 1.0 / np.sqrt(E)
    k_pos = jnp.arange(Sk)

    asg = {1: "model", 4: None}
    if batch_axis:
        asg[0] = batch_axis
    if seq_shard:
        k, v = _gather_last(k), _gather_last(v)

    def mask(s, q_pos):
        if not causal:
            return s
        ok = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            ok = ok & (q_pos[:, None] - k_pos[None, :] < window)
        return jnp.where(ok[None, None, None], s, NEG_INF)

    if seq_shard and not seq_shard_chunked:
        # Megatron-SP-style: ONE reshard per layer — gather K/V to full
        # head_dim, shard q's position dim over 'model'.  No chunk scan:
        # the per-device score block is already 1/model_size of (Sq, Sk).
        # (§Perf iter 5 — REFUTED: a hand-rolled bf16-materialized softmax
        # added more fusion boundaries than it saved; f32 softmax fuses
        # better. Kept the single-reshard structure from iter 2.)
        qg = _constrain_dims(q.reshape(B, Sq, G, M, E), asg)
        # (§Perf iter 6 — REFUTED: a q-major 'bsgmt' layout was tried to
        # remove a transpose+copy of the scores; it measured 5% WORSE —
        # the partitioner preferred the head-major layout.)
        s = jnp.einsum("bsgme,btge->bgmst", qg, k) * scale
        s = mask(s.astype(jnp.float32), jnp.arange(Sq))
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bgmst,btge->bsgme", p, v)
        return checkpoint_name(o.reshape(B, Sq, G * M, E), OUT_NAME)

    qg = q.reshape(B, Sq, G, M, E)
    q_chunk = min(q_chunk, Sq)
    n_full, tail = divmod(Sq, q_chunk)
    obs.counter("models/attn_seq", impl="xla", ragged=int(tail > 0),
                causal=int(causal)).inc()

    def one_chunk(start, size):
        qs = jax.lax.dynamic_slice_in_dim(qg, start, size, 1)
        if seq_shard:
            # forward-only paths (prefill): the q-chunk scan ON TOP of the
            # sequence sharding bounds the materialized score block to
            # (q_chunk/16, Sk) per device — the per-chunk reshard is cheap
            # when there is no backward pass to mirror it (§Perf pair-B
            # iter 3).
            qs = _constrain_dims(qs, asg)
        s = jnp.einsum("bcgme,btge->bgmct", qs, k) * scale
        s = mask(s.astype(jnp.float32), start + jnp.arange(size))
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bgmct,btge->bcgme", p, v)

    if not seq_shard:
        # training: each chunk's scores are recomputed in the backward
        one_chunk = jax.checkpoint(one_chunk, static_argnums=(1,))
    if n_full == 1:
        parts = [one_chunk(0, q_chunk)]
    else:
        _, os_ = jax.lax.scan(
            lambda c, i: (c, one_chunk(i * q_chunk, q_chunk)), 0,
            jnp.arange(n_full))
        parts = [jnp.moveaxis(os_, 0, 1).reshape(B, n_full * q_chunk, G, M, E)]
    if tail:
        parts.append(one_chunk(n_full * q_chunk, tail))
    o = jnp.concatenate(parts, axis=1) if tail else parts[0]
    return checkpoint_name(o.reshape(B, Sq, G * M, E), OUT_NAME)


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

_PALLAS_ANNOUNCED = set()


def _announce_pallas(tag):
    """Trace-time marker that the pallas decode-attn branch was actually
    taken inside the jitted decode — the CI serve smoke greps for it."""
    if tag not in _PALLAS_ANNOUNCED:
        _PALLAS_ANNOUNCED.add(tag)
        print(f"[attn] decode-attn path: pallas ({tag})", flush=True)


def _gather_pages(pages, table):
    """Physical pages (n_pages, P, KV, E) + table (B, W) → the logical
    dense cache (B, W·P, KV, E).  The gathered array is value-identical
    to a dense cache holding the same content, so the jax paged path is
    bit-exact vs dense — the einsums see the same operands."""
    n_pages, P, KV, E = pages.shape
    B, W = table.shape
    return pages[table].reshape(B, W * P, KV, E)


def attn_decode(q, k_cache, v_cache, pos, *, window=None,
                seq_shard: bool = False, impl: str = "jax",
                interpret=None, page_table=None, page_size: int = 0):
    """q: (B,1,H,E); caches: (B,S,KV,E) already containing the new token at
    index ``pos``.  Masks out positions > pos and outside the window.

    impl='pallas' streams the cache through the Pallas decode kernel
    (seq_shard stays on the jax path: the sharding constraints live
    outside the kernel grid).

    ``page_table`` (B, W) selects the PAGED cache layout: k/v are
    physical page pools (n_pages, page_size, KV, E) and position t lives
    at ``pages[table[b, t // P], t % P]``.  impl='pallas' walks the
    table inside the kernel (scalar-prefetched index maps); the jax path
    gathers the pages into the logical dense cache and reuses the dense
    math unchanged."""
    if page_table is not None:
        if impl == "pallas" and not seq_shard:
            from repro.kernels.decode_attention import paged_decode_attention

            _announce_pallas("paged")
            return paged_decode_attention(q, k_cache, v_cache, page_table,
                                          pos, window=window,
                                          interpret=interpret)
        k_cache = _gather_pages(k_cache, page_table)
        v_cache = _gather_pages(v_cache, page_table)
    if impl == "pallas" and not seq_shard:
        from repro.kernels.decode_attention import decode_attention

        _announce_pallas("canonical")
        return decode_attention(q, k_cache, v_cache, pos, window=window,
                                interpret=interpret)
    if seq_shard:
        q = _gather_last(q)  # head_dim-sharded projections -> gather tiny q
    B, _, H, E = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G, M = KV, H // KV
    qg = q.reshape(B, G, M, E)
    s = jnp.einsum("bgme,btge->bgmt", qg, k_cache) / np.sqrt(E)
    s = s.astype(jnp.float32)
    t = jnp.arange(S)
    ok = t <= pos
    if window is not None:
        ok = ok & (pos - t < window)
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bgmt,btge->bgme", p, v_cache)
    return o.reshape(B, 1, H, E)


def attn_decode_delta(q, k_cache, v_cache, k_new, v_new, pos, *,
                      window=None, seq_shard: bool = False,
                      impl: str = "jax", interpret=None,
                      page_table=None, page_size: int = 0):
    """Decode WITHOUT writing the cache first: attend over the old cache
    (positions < pos) plus an explicit extra column for the new token.

    Mathematically identical to update-then-attend, but the full per-layer
    cache never flows through the layer scan — the new K/V rows are emitted
    as scan outputs and written back with ONE stacked dynamic-update-slice
    per step (§Perf pair-D): decode stops depending on XLA's while-loop
    buffer aliasing for ~TB-scale cache copies.

    impl='pallas' uses the fused kernel variant: the new-token column is
    folded into the online-softmax init, so the cache is read exactly once
    and the concat-and-resoftmax disappears.

    ``page_table`` selects the paged cache layout exactly as in
    :func:`attn_decode` (the decode hot path under ``--cache paged``:
    the cache write happens OUTSIDE attention, so pages are read-only
    here and prefix-shared pages need no special casing).
    """
    if page_table is not None:
        if impl == "pallas" and not seq_shard:
            from repro.kernels.decode_attention import paged_decode_attention

            _announce_pallas("paged-delta")
            return paged_decode_attention(q, k_cache, v_cache, page_table,
                                          pos, window=window,
                                          k_new=k_new, v_new=v_new,
                                          interpret=interpret)
        k_cache = _gather_pages(k_cache, page_table)
        v_cache = _gather_pages(v_cache, page_table)
    if impl == "pallas" and not seq_shard:
        from repro.kernels.decode_attention import decode_attention

        _announce_pallas("delta")
        return decode_attention(q, k_cache, v_cache, pos, window=window,
                                k_new=k_new, v_new=v_new,
                                interpret=interpret)
    if seq_shard:
        q = _gather_last(q)
        k_new = _gather_last(k_new)
        v_new = _gather_last(v_new)
    B, _, H, E = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G, M = KV, H // KV
    qg = q.reshape(B, G, M, E)
    s_old = jnp.einsum("bgme,btge->bgmt", qg, k_cache) / np.sqrt(E)
    s_old = s_old.astype(jnp.float32)
    t = jnp.arange(S)
    ok = t < pos                      # strictly old positions
    if window is not None:
        ok = ok & (pos - t < window)
    s_old = jnp.where(ok[None, None, None], s_old, NEG_INF)
    s_new = (jnp.einsum("bgme,bge->bgm", qg, k_new[:, 0])
             / np.sqrt(E)).astype(jnp.float32)[..., None]
    s = jnp.concatenate([s_old, s_new], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = (jnp.einsum("bgmt,btge->bgme", p[..., :S], v_cache)
         + p[..., S:] * v_new[:, 0][:, :, None, :])
    return o.reshape(B, 1, H, E)


def write_new_token(cache, new, pos, *, layer_stacked: bool = True):
    """cache (L,B,S,KV,E) [or (B,S,KV,E)]; new (L,B,1,KV,E) [or (B,1,..)];
    single write of the new token column at dynamic index pos."""
    axis = 2 if layer_stacked else 1
    return jax.lax.dynamic_update_slice_in_dim(
        cache, new.astype(cache.dtype), pos, axis=axis)


def write_new_token_paged(cache, new, page_table, pos, page_size: int):
    """Paged counterpart of :func:`write_new_token`: cache is the page
    pool (L, n_pages, P, KV, E), new (L, B, 1, KV, E); request b's new
    column lands at physical ``(page_table[b, pos // P], pos % P)``.
    One scatter per step, same as the dense single dynamic-update-slice.
    COW happens host-side BEFORE this write (serving/kvpool.py), so the
    target page is always exclusively owned."""
    j = pos // page_size
    off = pos % page_size
    page_ids = jnp.take(page_table, j, axis=1)        # (B,)
    return cache.at[:, page_ids, off].set(new[:, :, 0].astype(cache.dtype))


def update_cache(cache, new, pos):
    """cache (B,S,KV,E); new (B,1,KV,E); write at dynamic index pos."""
    return jax.lax.dynamic_update_slice_in_dim(cache, new.astype(cache.dtype),
                                               pos, axis=1)
