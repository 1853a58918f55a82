"""Whisper encoder-decoder (arXiv:2212.04356).

Encoder: log-mel frames (B, 2 * S_enc, n_mels) through the conv front
end (conv1d n_mels -> d, kernel 3; GELU; conv1d d -> d, kernel 3, stride
2; GELU), plus sinusoidal positions, then pre-LayerNorm layers of
self-attention and FFN, and a final LayerNorm.  Decoder: token embedding
plus learned positions, pre-LayerNorm layers of causal self-attention,
cross-attention over the encoder output and FFN, a final LayerNorm, and
logits through the tied embedding.  The teacher-forced pass, ``prefill``
and ``decode_step`` share the front end (:func:`encode`) and the token
positions (:func:`_embed`).

The parts run under ``jax.named_scope`` ``frontend``, ``encoder``,
``decoder``, ``self_attn``, ``cross_attn`` and ``out_head``, which land
in the compiled ops' metadata.

Under ``cfg.remat`` each encoder and decoder layer is rematerialised
with :data:`LAYER_POLICY`: the backward keeps each attention's q/k/v
projections, its output and its output projection, and recomputes the
LayerNorms, residual adds and the FFN, and (inside ``attn_seq``'s own
per-chunk checkpoint) the attention scores.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro import obs
from repro.models import attention as A
from repro.models import ffn as F
from repro.models.common import (apply_norm, cross_entropy, gelu, norm_spec,
                                 sinusoidal_positions)
from repro.models.transformer import _pad_cache, _stack
from repro.sharding import ParamSpec

CONV_K = 3      # both front-end convolutions: kernel 3, padding 1

# what a layer's backward keeps: each attention's q/k/v projections, its
# output and its output projection.  The FFN's (B, S, d_ff) hidden would
# take as much again, past what one chip holds beside four learners.
ATTN_QKV, ATTN_OUT, ATTN_PROJ = "attn_qkv", "attn_out", "attn_proj"
LAYER_POLICY = jax.checkpoint_policies.save_only_these_names(
    ATTN_QKV, ATTN_OUT, ATTN_PROJ)


def enc_layer_specs(cfg):
    return {
        "ln1": norm_spec(cfg),
        "attn": A.attn_param_specs(cfg),
        "ln2": norm_spec(cfg),
        "mlp": F.ffn_param_specs(cfg),
    }


def dec_layer_specs(cfg):
    return {
        "ln1": norm_spec(cfg),
        "self_attn": A.attn_param_specs(cfg),
        "lnx": norm_spec(cfg),
        "cross_attn": A.attn_param_specs(cfg),
        "ln2": norm_spec(cfg),
        "mlp": F.ffn_param_specs(cfg),
    }


def _conv_specs(cfg, c_in):
    """(kernel tap, in channel, out channel) weights and a bias."""
    return {
        "w": ParamSpec((CONV_K, c_in, cfg.d_model), cfg.param_dtype,
                       (None, None, "embed"), "normal",
                       (CONV_K * c_in) ** -0.5),
        "b": ParamSpec((cfg.d_model,), "float32", ("embed",), "zeros"),
    }


def param_specs(cfg):
    return {
        "frontend": {"conv1": _conv_specs(cfg, cfg.n_mels),
                     "conv2": _conv_specs(cfg, cfg.d_model)},
        "embed": ParamSpec((cfg.vocab, cfg.d_model), cfg.param_dtype,
                           ("vocab", "embed"), "normal", 0.02),
        "dec_pos": ParamSpec((cfg.n_text_ctx, cfg.d_model), cfg.param_dtype,
                             (None, "embed"), "normal", 0.02),
        "enc_layers": _stack(enc_layer_specs(cfg), cfg.n_enc_layers),
        "enc_norm": norm_spec(cfg),
        "dec_layers": _stack(dec_layer_specs(cfg), cfg.n_layers),
        "dec_norm": norm_spec(cfg),
    }


def conv1d(p, x, stride: int):
    """Kernel-3 convolution over time with padding 1: x (B, T, C) ->
    (B, (T - 1) // stride + 1, D), the three taps side by side as one
    matmul of depth 3C."""
    B, T, C = x.shape
    n = (T - 1) // stride + 1
    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0)))
    taps = jnp.concatenate([xp[:, i:i + stride * (n - 1) + 1:stride]
                            for i in range(CONV_K)], axis=-1)
    y = jnp.einsum("btc,cd->btd", taps, p["w"].reshape(CONV_K * C, -1))
    return (y.astype(jnp.float32) + p["b"]).astype(y.dtype)


def frontend(params, mel):
    """mel: (B, 2 * S_enc, n_mels) -> (B, S_enc, d) with positions."""
    with jax.named_scope("frontend"):
        p = params["frontend"]
        x = gelu(conv1d(p["conv1"], mel.astype(jnp.bfloat16), 1))
        x = gelu(conv1d(p["conv2"], x, 2))
        S, d = x.shape[1], x.shape[2]
        return x + sinusoidal_positions(jnp.arange(S), d).astype(x.dtype)


def _attention(cfg, p, xq, xkv, **kw):
    """Projections, ``attn_seq`` and the output projection, each output
    named for :data:`LAYER_POLICY`; returns the output and (k, v)."""
    q, k, v = (checkpoint_name(t, ATTN_QKV)
               for t in A.qkv_project(cfg, p, xq, xkv))
    o = checkpoint_name(A.attn_seq(q, k, v, **kw), ATTN_OUT)
    return checkpoint_name(A.out_project(p, o), ATTN_PROJ), (k, v)


def _remat(cfg, layer, stack: str):
    """The layer body a stack scans: under ``cfg.remat`` checkpointed
    with :data:`LAYER_POLICY` (``prevent_cse=False``: the scan already
    keeps the recompute apart), else as it is.  Each trace counts once
    in the ``repro.obs`` counter ``models/remat``, tagged ``stack``
    (``enc``/``dec``) and ``policy`` (``dots+attn_out``/``none``)."""
    obs.counter("models/remat", stack=stack,
                policy="dots+attn_out" if cfg.remat else "none").inc()
    if not cfg.remat:
        return layer
    return jax.checkpoint(layer, policy=LAYER_POLICY, prevent_cse=False)


def encode(cfg, params, mel, *, batch_axis="", fwd_only=False):
    """mel: (B, 2 * S_enc, n_mels) log-mel frames -> (B, S_enc, d)."""
    x = frontend(params, mel)
    seq_shard = cfg.attn_sharding == "seq"

    def layer(x, p):
        h = apply_norm(p["ln1"], x)
        with jax.named_scope("self_attn"):
            y, _ = _attention(cfg, p["attn"], h, h, causal=False,
                              seq_shard=seq_shard,
                              seq_shard_chunked=seq_shard and fwd_only,
                              batch_axis=batch_axis)
            x = x + y
        x = x + F.ffn_apply(cfg, p["mlp"], apply_norm(p["ln2"], x))
        return x.astype(jnp.bfloat16), None

    with jax.named_scope("encoder"):
        x, _ = jax.lax.scan(_remat(cfg, layer, "enc"), x,
                            params["enc_layers"])
        return apply_norm(params["enc_norm"], x)


def _embed(params, tokens, start):
    """Token embeddings plus the learned positions start, start + 1, ..."""
    pos = jax.lax.dynamic_slice_in_dim(params["dec_pos"], start,
                                       tokens.shape[1], 0)
    return (params["embed"][tokens] + pos[None]).astype(jnp.bfloat16)


def decode_seq(cfg, params, tokens, enc_out, *, collect_cache=False,
               cache_len=0, batch_axis=""):
    """Teacher-forced decoder over a full token sequence."""
    x = _embed(params, tokens, 0)
    seq_shard = cfg.attn_sharding == "seq"
    chunked = seq_shard and collect_cache

    def layer(x, p):
        h = apply_norm(p["ln1"], x)
        with jax.named_scope("self_attn"):
            y, (k, v) = _attention(cfg, p["self_attn"], h, h, causal=True,
                                   seq_shard=seq_shard,
                                   seq_shard_chunked=chunked,
                                   batch_axis=batch_axis)
            x = x + y
        h = apply_norm(p["lnx"], x)
        with jax.named_scope("cross_attn"):
            y, (ck, cv) = _attention(cfg, p["cross_attn"], h, enc_out,
                                     causal=False, seq_shard=seq_shard,
                                     seq_shard_chunked=chunked,
                                     batch_axis=batch_axis)
            x = x + y
        x = x + F.ffn_apply(cfg, p["mlp"], apply_norm(p["ln2"], x))
        cache = (_pad_cache(k, v, cache_len), (ck, cv)) if collect_cache else ()
        return x.astype(jnp.bfloat16), cache

    with jax.named_scope("decoder"):
        x, caches = jax.lax.scan(_remat(cfg, layer, "dec"), x,
                                 params["dec_layers"])
        return apply_norm(params["dec_norm"], x), caches


def _logits(params, x):
    """The output head, tied to the embedding."""
    with jax.named_scope("out_head"):
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])


def loss_train(cfg, params, batch, *, batch_axis="", **_):
    enc_out = encode(cfg, params, batch["mel"], batch_axis=batch_axis)
    x, _ = decode_seq(cfg, params, batch["tokens"], enc_out,
                      batch_axis=batch_axis)
    logits = _logits(params, x)
    with jax.named_scope("out_head"):
        return cross_entropy(logits, batch["labels"])


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def cache_specs(cfg, batch: int, cache_len: int, enc_len: int):
    kv = lambda s: {
        "k": ParamSpec((cfg.n_layers, batch, s, cfg.n_kv_heads, cfg.head_dim),
                       "bfloat16",
                       ("layers", "batch", "cache_seq", "kv_heads",
                        "head_dim")),
        "v": ParamSpec((cfg.n_layers, batch, s, cfg.n_kv_heads, cfg.head_dim),
                       "bfloat16",
                       ("layers", "batch", "cache_seq", "kv_heads",
                        "head_dim")),
    }
    return {"self": kv(cache_len), "cross": kv(enc_len)}


def prefill(cfg, params, mel, tokens, *, cache_len: int = 0,
            batch_axis="data"):
    enc_out = encode(cfg, params, mel, batch_axis=batch_axis, fwd_only=True)
    cache_len = cache_len or tokens.shape[1]
    x, caches = decode_seq(cfg, params, tokens, enc_out,
                           collect_cache=True, cache_len=cache_len,
                           batch_axis=batch_axis)
    (k, v), (ck, cv) = caches
    logits = _logits(params, x[:, -1:, :])
    return logits, {"self": {"k": k, "v": v}, "cross": {"k": ck, "v": cv}}


def decode_step(cfg, params, cache, tokens, pos):
    """One decoder token against self+cross caches."""
    x = _embed(params, tokens, pos)

    def layer(x, scanned):
        p, cache_l = scanned
        h = apply_norm(p["ln1"], x)
        with jax.named_scope("self_attn"):
            q, k, v = A.qkv_project(cfg, p["self_attn"], h, h)
            kc = A.update_cache(cache_l["self"]["k"], k, pos)
            vc = A.update_cache(cache_l["self"]["v"], v, pos)
            o = A.attn_decode(q, kc, vc, pos)
            x = x + A.out_project(p["self_attn"], o)
        h = apply_norm(p["lnx"], x)
        with jax.named_scope("cross_attn"):
            q = jnp.einsum("bsd,dhe->bshe", h, p["cross_attn"]["wq"])
            if "bq" in p["cross_attn"]:
                q = (q.astype(jnp.float32)
                     + p["cross_attn"]["bq"]).astype(q.dtype)
            ck, cv = cache_l["cross"]["k"], cache_l["cross"]["v"]
            o = A.attn_decode(q, ck, cv, jnp.int32(ck.shape[1] - 1))
            x = x + A.out_project(p["cross_attn"], o)
        x = x + F.ffn_apply(cfg, p["mlp"], apply_norm(p["ln2"], x))
        return x.astype(jnp.bfloat16), {"self": {"k": kc, "v": vc},
                                        "cross": {"k": ck, "v": cv}}

    with jax.named_scope("decoder"):
        x, new_cache = jax.lax.scan(layer, x, (params["dec_layers"], cache))
        x = apply_norm(params["dec_norm"], x)
    return _logits(params, x), new_cache
