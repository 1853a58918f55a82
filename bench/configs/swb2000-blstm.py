"""Plain reference of the paper's acoustic model (swb2000-blstm.json).

Cui et al. 2020, Section V: 260-dim acoustic features into 6
bidirectional LSTM layers of ``lstm_hidden`` cells per direction, a
linear bottleneck of ``lstm_bottleneck`` units, and a ``vocab``-way
softmax over CD-HMM states, trained with frame-level cross-entropy.

The LSTM cell: gates [i | f | g | o] = x Wx + h Wh + b;
c' = sigmoid(f + 1) c + sigmoid(i) tanh(g); h' = sigmoid(o) tanh(c').
The backward direction runs the same recurrence over reversed time; the
layer output is [forward | backward].  Everything is float32; ``einsum``
carries the matmul precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layout(s: dict) -> dict:
    """path -> (shape, dtype, kind, std), the paths of the program's
    parameter tree."""
    H, wdt = s["lstm_hidden"], s["param_dtype"]
    out = {}
    d_in = s["input_dim"]
    for i in range(s["n_layers"]):
        for d in ("fwd", "bwd"):
            pre = f"layers/layer_{i}/{d}/"
            out[pre + "wx"] = ((d_in, 4 * H), wdt, "normal", d_in ** -0.5)
            out[pre + "wh"] = ((H, 4 * H), wdt, "normal", H ** -0.5)
            out[pre + "b"] = ((4 * H,), "float32", "zeros", 0.0)
        d_in = 2 * H
    out["bottleneck"] = ((2 * H, s["lstm_bottleneck"]), wdt, "normal",
                         (2 * H) ** -0.5)
    out["softmax_w"] = ((s["lstm_bottleneck"], s["vocab"]), wdt, "normal",
                        0.02)
    out["softmax_b"] = ((s["vocab"],), "float32", "zeros", 0.0)
    return out


def _lstm(wx, wh, b, x, reverse, einsum):
    gx = einsum("btd,dg->tbg", x, wx) + b
    H = wh.shape[0]
    B = x.shape[0]

    def cell(carry, g_t):
        h, c = carry
        g = g_t + einsum("bh,hg->bg", h, wh)
        i, f, u, o = (g[:, k * H:(k + 1) * H] for k in range(4))
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(u)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((B, H), jnp.float32)
    _, hs = jax.lax.scan(cell, (zero, zero), gx, reverse=reverse)
    return jnp.swapaxes(hs, 0, 1)


def loss(s: dict, p: dict, batch: dict, *, einsum) -> jax.Array:
    """Mean frame cross-entropy of one learner's batch."""
    x = batch["features"].astype(jnp.float32)
    for i in range(s["n_layers"]):
        pre = f"layers/layer_{i}/"
        x = jnp.concatenate(
            [_lstm(p[pre + f"{d}/wx"], p[pre + f"{d}/wh"], p[pre + f"{d}/b"],
                   x, d == "bwd", einsum) for d in ("fwd", "bwd")], -1)
    z = einsum("btd,dk->btk", x, p["bottleneck"])
    logits = einsum("btk,kv->btv", z, p["softmax_w"]) + p["softmax_b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def fwd_flops_per_frame(s: dict) -> int:
    """Matmul FLOPs of one frame's forward pass."""
    H = s["lstm_hidden"]
    flops, d_in = 0, s["input_dim"]
    for _ in range(s["n_layers"]):
        flops += 2 * 2 * 4 * H * (d_in + H)     # two directions
        d_in = 2 * H
    flops += 2 * d_in * s["lstm_bottleneck"]
    flops += 2 * s["lstm_bottleneck"] * s["vocab"]
    return flops


def model_flops_per_step(s: dict, traffic: dict) -> float:
    """Forward + backward (3x the forward) over the global batch."""
    frames = (traffic["learners"] * traffic["batch_per_learner"]
              * traffic["frames"])
    return 3.0 * fwd_flops_per_frame(s) * frames


def audio_seconds_per_step(s: dict, traffic: dict) -> float:
    return (traffic["learners"] * traffic["batch_per_learner"]
            * traffic["frames"] * s["frame_seconds"])


def input_width(s: dict) -> int:
    return s["input_dim"]
