"""Unified decoder-only stack covering the dense / moe / ssm / hybrid / vlm
families.  Layers are stacked into one scanned pytree (small HLO, bounded
compile time at 40+ layers); per-layer heterogeneity (sliding-window vs
global attention in hymba / llama4-scout) rides through the scan as data.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A
from repro.models import ffn as F
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.common import apply_norm, cross_entropy, norm_spec, rmsnorm
from repro.sharding import ParamSpec

GLOBAL_WINDOW = np.int32(2**30)   # "window" meaning full attention


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def layer_param_specs(cfg):
    fam = cfg.family
    p = {"ln1": norm_spec(cfg)}
    if fam in ("dense", "moe", "hybrid", "vlm"):
        p["attn"] = A.attn_param_specs(cfg)
    if fam in ("ssm", "hybrid"):
        p["ssm"] = S.ssm_param_specs(cfg)
    if fam in ("dense", "vlm", "hybrid"):
        p["ln2"] = norm_spec(cfg)
        p["mlp"] = F.ffn_param_specs(cfg)
    if fam == "moe":
        p["ln2"] = norm_spec(cfg)
        p["moe"] = M.moe_param_specs(cfg)
    return p


def _stack(spec_tree, n):
    def one(ps: ParamSpec):
        return ParamSpec((n,) + ps.shape, ps.dtype, ("layers",) + ps.axes,
                         ps.init, ps.init_scale)
    return jax.tree.map(one, spec_tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_specs(cfg):
    d, V = cfg.d_model, cfg.vocab
    p = {
        "embed": ParamSpec((V, d), cfg.param_dtype, ("vocab", "embed"),
                           "normal", 0.02),
        "layers": _stack(layer_param_specs(cfg), cfg.n_layers),
        "final_norm": norm_spec(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = ParamSpec((d, V), cfg.param_dtype,
                                 ("embed", "vocab"), "normal", 0.02)
    return p


def layer_windows(cfg, seq_len: int, *, long_context: bool = False):
    """Per-layer attention window array (n_layers,) int32."""
    w = cfg.window
    if long_context and w == 0:
        w = cfg.window_for_long   # documented sliding-window variant
    if w == 0:
        return np.full((cfg.n_layers,), GLOBAL_WINDOW, np.int32)
    ws = np.full((cfg.n_layers,), w, np.int32)
    for i in cfg.global_attn_layers:
        if i < cfg.n_layers:
            ws[i] = GLOBAL_WINDOW
    return ws


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _attn_block(cfg, p, x, positions, window, *, cache=None, pos=None,
                fwd_only: bool = False, batch_axis=""):
    """Returns (out, (k,v)) — k/v are the new cache when decoding, else the
    full-seq K/V for cache construction."""
    seq_shard = cfg.attn_sharding == "seq"
    if cache is None:
        q, k, v = A.qkv_project(cfg, p, x, x, positions, positions)
        o = A.attn_seq(q, k, v, causal=True, window=window,
                       seq_shard=seq_shard,
                       seq_shard_chunked=seq_shard and fwd_only,
                       batch_axis=batch_axis)
        return A.out_project(p, o), (k, v)
    k_cache, v_cache = cache
    q, k, v = A.qkv_project(cfg, p, x, x, positions, positions)
    k_cache = A.update_cache(k_cache, k, pos)
    v_cache = A.update_cache(v_cache, v, pos)
    o = A.attn_decode(q, k_cache, v_cache, pos, window=window,
                      seq_shard=seq_shard)
    return A.out_project(p, o), (k_cache, v_cache)


def _hybrid_combine(attn_out, ssm_out):
    # Hymba: per-branch output normalization then mean fusion
    return 0.5 * (rmsnorm(attn_out) + rmsnorm(ssm_out))


# ---------------------------------------------------------------------------
# Sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def forward_seq(cfg, params, x, *, long_context: bool = False,
                collect_cache: bool = False, cache_len: int = 0,
                kernel_impl: str = "jax", batch_axis=""):
    """x: (B,S,d) embedded inputs.  Returns (hidden, aux_loss, cache)."""
    Bsz, Ssz, _ = x.shape
    windows = jnp.asarray(layer_windows(cfg, Ssz, long_context=long_context))
    positions = jnp.arange(Ssz)[None, :]
    fam = cfg.family

    def layer(x, scanned):
        p, window = scanned
        aux = jnp.float32(0.0)
        h = apply_norm(p["ln1"], x)
        cache_out = ()
        if fam in ("dense", "moe", "vlm"):
            o, (k, v) = _attn_block(cfg, p["attn"], h, positions, window,
                                    fwd_only=collect_cache,
                                    batch_axis=batch_axis)
            x = x + o
            if collect_cache:
                cache_out = _pad_cache(k, v, cache_len)
        elif fam == "ssm":
            o, (conv_state, h_ssm) = S.mamba2_seq(cfg, p["ssm"], h,
                                                  kernel_impl=kernel_impl)
            x = x + o
            if collect_cache:
                cache_out = (conv_state, h_ssm)
        elif fam == "hybrid":
            oa, (k, v) = _attn_block(cfg, p["attn"], h, positions, window,
                                     fwd_only=collect_cache,
                                     batch_axis=batch_axis)
            os_, (conv_state, h_ssm) = S.mamba2_seq(cfg, p["ssm"], h,
                                                    kernel_impl=kernel_impl)
            x = x + _hybrid_combine(oa, os_).astype(x.dtype)
            if collect_cache:
                cache_out = (_pad_cache(k, v, cache_len), conv_state, h_ssm)
        if fam in ("dense", "vlm", "hybrid"):
            x = x + F.ffn_apply(cfg, p["mlp"], apply_norm(p["ln2"], x))
        elif fam == "moe":
            mo, aux = M.moe_apply(cfg, p["moe"], apply_norm(p["ln2"], x))
            x = x + mo
        return x.astype(jnp.bfloat16), (aux, cache_out)

    body = jax.checkpoint(layer) if cfg.remat else layer

    def scan_body(x, scanned):
        return body(x, scanned)

    x, (auxes, caches) = jax.lax.scan(scan_body, x.astype(jnp.bfloat16),
                                      (params["layers"], windows))
    return x, jnp.sum(auxes), caches


def _pad_cache(k, v, cache_len):
    """Grow prefill K/V to the serving cache length (zero-padded tail)."""
    if cache_len and cache_len > k.shape[1]:
        pad = ((0, 0), (0, cache_len - k.shape[1]), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return (k, v)


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------

def embed_tokens(cfg, params, tokens):
    return params["embed"][tokens].astype(jnp.bfloat16)


def logits_fn(cfg, params, x):
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


def embed_with_prefix(cfg, params, tokens, patches):
    """VLM early fusion: prefix patch embeddings then text tokens."""
    xt = embed_tokens(cfg, params, tokens)
    if patches is not None:
        xp = patches.astype(jnp.bfloat16)
        return jnp.concatenate([xp, xt], axis=1)
    return xt


# ---------------------------------------------------------------------------
# Train loss
# ---------------------------------------------------------------------------

def loss_train(cfg, params, batch, *, kernel_impl: str = "jax",
               batch_axis=""):
    """batch: {'tokens','labels'} (+ 'patches' for vlm)."""
    tokens, labels = batch["tokens"], batch["labels"]
    patches = batch.get("patches")
    x = embed_with_prefix(cfg, params, tokens, patches)
    x, aux, _ = forward_seq(cfg, params, x, kernel_impl=kernel_impl,
                            batch_axis=batch_axis)
    x = apply_norm(params["final_norm"], x)
    if patches is not None:   # loss only over text positions
        x = x[:, patches.shape[1]:, :]
    logits = logits_fn(cfg, params, x)
    loss = cross_entropy(logits, labels)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux
    return loss


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

def cache_specs(cfg, batch: int, cache_len: int):
    """Stacked per-layer decode-state specs for this family."""
    fam = cfg.family
    kv = lambda: {
        "k": ParamSpec((cfg.n_layers, batch, cache_len, cfg.n_kv_heads,
                        cfg.head_dim),
                       "bfloat16",
                       ("layers", "batch", "cache_seq", "kv_heads",
                        "head_dim")),
        "v": ParamSpec((cfg.n_layers, batch, cache_len, cfg.n_kv_heads,
                        cfg.head_dim),
                       "bfloat16",
                       ("layers", "batch", "cache_seq", "kv_heads",
                        "head_dim")),
    }
    ssm = lambda: jax.tree.map(
        lambda ps: ParamSpec((cfg.n_layers,) + ps.shape, ps.dtype,
                             ("layers",) + ps.axes),
        S.ssm_cache_specs(cfg, batch),
        is_leaf=lambda x: isinstance(x, ParamSpec))
    if fam in ("dense", "moe", "vlm"):
        return {"attn": kv()}
    if fam == "ssm":
        return {"ssm": ssm()}
    if fam == "hybrid":
        return {"attn": kv(), "ssm": ssm()}
    raise ValueError(fam)


def page_specs(cfg, n_pages: int, page_size: int):
    """Paged decode-state specs: ONE pool of physical KV pages shared by
    every in-flight request (serve.py ``--cache paged``) instead of a
    per-slot (batch, cache_len) row.  Attention-only families."""
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"paged KV cache needs an attention-only family, "
                         f"got {cfg.family}")
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    axes = ("layers", "pages", "page_pos", "kv_heads", "head_dim")
    return {"attn": {"k": ParamSpec(shape, "bfloat16", axes),
                     "v": ParamSpec(shape, "bfloat16", axes)}}


def decode_step(cfg, params, cache, tokens, pos, *, long_context: bool = False,
                kernel_impl: str = "jax", page_table=None, page_size: int = 0):
    """One-token decode.  tokens: (B,1) int32, pos: scalar int32 position of
    the new token.  Returns (logits (B,1,V), new cache).

    kernel_impl='pallas' routes the per-layer attention through the fused
    Pallas decode kernel (cfg.attn_decode_impl overrides when set).

    ``page_table`` (B, W) selects the PAGED cache layout (serve.py
    ``--cache paged``): cache['attn'] k/v are page pools
    (L, n_pages, page_size, KV, E) shared across requests, the attention
    walks the table, and the new-token column scatters into the table's
    page for ``pos`` (the page is exclusively owned — COW runs host-side
    first).  Attention-only families; SSM/hybrid state is per-slot O(1)
    and has nothing to page."""
    fam = cfg.family
    paged = page_table is not None
    if paged and fam not in ("dense", "moe", "vlm"):
        raise ValueError(f"paged KV cache needs an attention-only family, "
                         f"got {fam}")
    if paged:
        S_cache = page_table.shape[-1] * page_size   # logical length
    else:
        S_cache = (cache["attn"]["k"].shape[2] if "attn" in cache
                   else (1 << 30))
    windows = jnp.asarray(layer_windows(cfg, S_cache,
                                        long_context=long_context))
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.full((tokens.shape[0], 1), pos)
    seq_shard = cfg.attn_sharding == "seq"
    attn_impl = cfg.attn_decode_impl or kernel_impl

    def attn_delta(p, h, cache_l, window):
        q, k, v = A.qkv_project(cfg, p, h, h, positions, positions)
        o = A.attn_decode_delta(q, cache_l["attn"]["k"],
                                cache_l["attn"]["v"], k, v, pos,
                                window=window, seq_shard=seq_shard,
                                impl=attn_impl, page_table=page_table,
                                page_size=page_size)
        return A.out_project(p, o), {"k": k, "v": v}   # new-token rows only

    def layer(x, scanned):
        p, window, cache_l = scanned
        h = apply_norm(p["ln1"], x)
        new_cache = {}
        if fam in ("dense", "moe", "vlm"):
            o, kv_new = attn_delta(p["attn"], h, cache_l, window)
            x = x + o
            new_cache["attn"] = kv_new
        elif fam == "ssm":
            o, (conv_state, h_ssm) = S.mamba2_step(
                cfg, p["ssm"], h, cache_l["ssm"]["conv"], cache_l["ssm"]["h"])
            x = x + o
            new_cache["ssm"] = {"conv": conv_state, "h": h_ssm}
        elif fam == "hybrid":
            oa, kv_new = attn_delta(p["attn"], h, cache_l, window)
            os_, (conv_state, h_ssm) = S.mamba2_step(
                cfg, p["ssm"], h, cache_l["ssm"]["conv"], cache_l["ssm"]["h"])
            x = x + _hybrid_combine(oa, os_).astype(x.dtype)
            new_cache["attn"] = kv_new
            new_cache["ssm"] = {"conv": conv_state, "h": h_ssm}
        if fam in ("dense", "vlm", "hybrid"):
            x = x + F.ffn_apply(cfg, p["mlp"], apply_norm(p["ln2"], x))
        elif fam == "moe":
            mo, _ = M.moe_apply(cfg, p["moe"], apply_norm(p["ln2"], x))
            x = x + mo
        return x.astype(jnp.bfloat16), new_cache

    x, deltas = jax.lax.scan(layer, x.astype(jnp.bfloat16),
                             (params["layers"], windows, cache))
    # ONE stacked write of the new token column per step (§Perf pair-D):
    # the full caches never flow through the layer scan as outputs.
    new_cache = dict(cache)
    if "attn" in deltas:
        if paged:
            new_cache["attn"] = {
                "k": A.write_new_token_paged(cache["attn"]["k"],
                                             deltas["attn"]["k"],
                                             page_table, pos, page_size),
                "v": A.write_new_token_paged(cache["attn"]["v"],
                                             deltas["attn"]["v"],
                                             page_table, pos, page_size),
            }
        else:
            new_cache["attn"] = {
                "k": A.write_new_token(cache["attn"]["k"],
                                       deltas["attn"]["k"], pos),
                "v": A.write_new_token(cache["attn"]["v"],
                                       deltas["attn"]["v"], pos),
            }
    if "ssm" in deltas:
        new_cache["ssm"] = deltas["ssm"]   # O(1)-size states, stacked by scan
    x = apply_norm(params["final_norm"], x)
    return logits_fn(cfg, params, x), new_cache


def prefill(cfg, params, tokens, *, cache_len: int = 0, patches=None,
            long_context: bool = False, kernel_impl: str = "jax",
            batch_axis="data"):
    """Full-context forward emitting the decode cache + last-token logits."""
    fam = cfg.family
    x = embed_with_prefix(cfg, params, tokens, patches)
    cache_len = cache_len or x.shape[1]
    x, _, caches = forward_seq(cfg, params, x, collect_cache=True,
                               cache_len=cache_len,
                               long_context=long_context,
                               kernel_impl=kernel_impl,
                               batch_axis=batch_axis)
    x = apply_norm(params["final_norm"], x)
    logits = logits_fn(cfg, params, x[:, -1:, :])
    if fam in ("dense", "moe", "vlm"):
        k, v = caches
        cache = {"attn": {"k": k, "v": v}}
    elif fam == "ssm":
        conv_state, h_ssm = caches
        cache = {"ssm": {"conv": conv_state, "h": h_ssm}}
    elif fam == "hybrid":
        (k, v), conv_state, h_ssm = caches
        cache = {"attn": {"k": k, "v": v},
                 "ssm": {"conv": conv_state, "h": h_ssm}}
    else:
        raise ValueError(fam)
    return logits, cache
