"""Shared building blocks: norms, RoPE, losses, small numerics helpers.

All model code is functional pure-JAX: parameters are pytrees of arrays
described by :class:`repro.sharding.ParamSpec` trees.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import ParamSpec

DEFAULT_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_spec(cfg, dim=None, axes=("embed",)):
    dim = dim if dim is not None else cfg.d_model
    if cfg.norm == "layernorm":
        return {
            "scale": ParamSpec((dim,), "float32", axes, "ones"),
            "bias": ParamSpec((dim,), "float32", axes, "zeros"),
        }
    return {"scale": ParamSpec((dim,), "float32", axes, "ones")}


def apply_norm(p, x, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(dt)


def rmsnorm(x, scale=None, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    if scale is not None:
        y = y * scale
    return y.astype(dt)


# ---------------------------------------------------------------------------
# Rotary / sinusoidal positions
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, E); positions: broadcastable to (..., S)."""
    if theta <= 0:
        return x
    E = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(E, theta), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, E/2)
    ang = ang[..., None, :]                                      # (..., S, 1, E/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, d_model: int):
    """Whisper-style fixed sinusoidal embeddings; positions (..., S)."""
    half = d_model // 2
    freqs = jnp.exp(-np.log(10_000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def sequence_mask(lengths, max_len: int):
    """(B,) int lengths -> (B, max_len) bool validity mask.

    True at frames t < lengths[b] — the shared definition of "valid frame"
    used by the masked loss, the length-aware BLSTM, and CTC input
    masking (see the ``lengths`` batch contract in ``repro.data.pipeline``).
    """
    return jnp.arange(max_len)[None, :] < lengths[:, None]


def cross_entropy(logits, labels, z_loss: float = 0.0, mask=None):
    """Token-level CE; logits (..., V) any float dtype, labels (...) int.

    With ``mask`` (bool, same shape as labels) the loss is the sum over
    valid positions divided by the valid count — NOT the padded B*T mean —
    so padded frames neither dilute the loss nor leak into gradients."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    if mask is None:
        return jnp.mean(loss)
    m = mask.astype(jnp.float32)
    return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def dense_spec(d_in, d_out, axes, *, dtype="bfloat16", use_bias=False,
               out_axes=None, init="lecun"):
    p = {"w": ParamSpec((d_in, d_out), dtype, axes, init)}
    if use_bias:
        p["b"] = ParamSpec((d_out,), "float32", (axes[-1],), "zeros")
    return p


def dense(p, x):
    y = jnp.einsum("...d,df->...f", x, p["w"])
    if "b" in p:
        y = (y.astype(jnp.float32) + p["b"]).astype(y.dtype)
    return y


def gelu(x):
    """The exact GELU, x * Phi(x) through erf (``act="gelu"``, as
    whisper's published config names it), not the tanh approximation."""
    return jax.nn.gelu(x, approximate=False)
