"""Plain reference of whisper-large-v3 (whisper-large-v3.json).

Radford et al. 2022 (arXiv:2212.04356) and the published config.json:

* front end: log-mel frames (T, n_mels) -> conv1d to d_model (kernel 3,
  padding 1) -> GELU -> conv1d d_model -> d_model (kernel 3, padding 1,
  stride 2) -> GELU, plus sinusoidal positions (T / 2 of them);
* encoder layer: x += Attn(LN1(x)); x += FFN(LN2(x)); then a final LN;
* decoder: token embedding plus learned positions; layer: x +=
  CausalAttn(LN1(x)); x += CrossAttn(LNx(x), encoder output); x +=
  FFN(LN2(x)); then a final LN and logits through the embedding (tied);
* attention: q, v and out projections with a bias, k without, scores
  scaled by head_dim^-1/2; FFN: d -> d_ff -> d with biases and the exact
  GELU, x Phi(x) through erf; LayerNorm eps 1e-5;
* loss: mean next-token cross-entropy over the transcript.

Everything is float32; ``einsum`` carries the matmul precision.  The
layers of each stack run in a scan, each under ``jax.checkpoint`` (the
same arithmetic, recomputed in the backward), so that the reference fits
on the chip that held the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

CONV_K = 3


def _attn_layout(L, d, H, E, wdt):
    return {"wq": ((L, d, H, E), wdt, "normal", 0.02),
            "wk": ((L, d, H, E), wdt, "normal", 0.02),
            "wv": ((L, d, H, E), wdt, "normal", 0.02),
            "wo": ((L, H, E, d), wdt, "normal", 0.02),
            "bq": ((L, H, E), "float32", "zeros", 0.0),
            "bv": ((L, H, E), "float32", "zeros", 0.0),
            "bo": ((L, d), "float32", "zeros", 0.0)}


def _ln_layout(lead, d):
    return {"scale": (lead + (d,), "float32", "normal", 1.0),
            "bias": (lead + (d,), "float32", "zeros", 0.0)}


def _mlp_layout(L, d, ff, wdt):
    return {"wi": ((L, d, ff), wdt, "normal", 0.02),
            "wo": ((L, ff, d), wdt, "normal", 0.02),
            "bi": ((L, ff), "float32", "zeros", 0.0),
            "bo": ((L, d), "float32", "zeros", 0.0)}


def layout(s: dict) -> dict:
    """path -> (shape, dtype, kind, std), the paths of the program's
    parameter tree (layers stacked on a leading axis)."""
    d, H, E, ff = s["d_model"], s["n_heads"], s["head_dim"], s["d_ff"]
    wdt, Le, Ld = s["param_dtype"], s["n_enc_layers"], s["n_layers"]
    groups = {
        "frontend/conv1": {
            "w": ((CONV_K, s["n_mels"], d), wdt, "normal",
                  (CONV_K * s["n_mels"]) ** -0.5),
            "b": ((d,), "float32", "zeros", 0.0)},
        "frontend/conv2": {
            "w": ((CONV_K, d, d), wdt, "normal", (CONV_K * d) ** -0.5),
            "b": ((d,), "float32", "zeros", 0.0)},
        "enc_layers/ln1": _ln_layout((Le,), d),
        "enc_layers/attn": _attn_layout(Le, d, H, E, wdt),
        "enc_layers/ln2": _ln_layout((Le,), d),
        "enc_layers/mlp": _mlp_layout(Le, d, ff, wdt),
        "enc_norm": _ln_layout((), d),
        "dec_layers/ln1": _ln_layout((Ld,), d),
        "dec_layers/self_attn": _attn_layout(Ld, d, H, E, wdt),
        "dec_layers/lnx": _ln_layout((Ld,), d),
        "dec_layers/cross_attn": _attn_layout(Ld, d, H, E, wdt),
        "dec_layers/ln2": _ln_layout((Ld,), d),
        "dec_layers/mlp": _mlp_layout(Ld, d, ff, wdt),
        "dec_norm": _ln_layout((), d),
    }
    out = {f"{g}/{k}": v for g, leaves in groups.items()
           for k, v in leaves.items()}
    out["embed"] = ((s["vocab"], d), wdt, "normal", 0.02)
    out["dec_pos"] = ((s["n_text_ctx"], d), wdt, "normal", 0.02)
    return out


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _ln(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * scale + bias


def _conv(x, w, b, stride, einsum):
    """Kernel-3 convolution with padding 1 as a sum over its taps."""
    n = (x.shape[1] - 1) // stride + 1
    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0)))
    y = b
    for i in range(CONV_K):
        y = y + einsum("btc,cd->btd", xp[:, i:i + stride * (n - 1) + 1:stride],
                       w[i])
    return y


def _sinusoids(n, d):
    inc = math.log(10000.0) / (d // 2 - 1)
    t = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.exp(
        -inc * jnp.arange(d // 2, dtype=jnp.float32))[None]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], -1)


def _attn(p, xq, xkv, causal, einsum):
    E = p["wq"].shape[-1]
    q = einsum("bsd,dhe->bshe", xq, p["wq"]) + p["bq"]
    k = einsum("btd,dhe->bthe", xkv, p["wk"])
    v = einsum("btd,dhe->bthe", xkv, p["wv"]) + p["bv"]
    sc = einsum("bshe,bthe->bhst", q, k) / math.sqrt(E)
    if causal:
        S, T = sc.shape[-2:]
        sc = jnp.where(jnp.tril(jnp.ones((S, T), bool)), sc, -jnp.inf)
    o = einsum("bhst,bthe->bshe", jax.nn.softmax(sc, -1), v)
    return einsum("bshe,hed->bsd", o, p["wo"]) + p["bo"]


def _mlp(p, x, einsum):
    h = _gelu(einsum("bsd,df->bsf", x, p["wi"]) + p["bi"])
    return einsum("bsf,fd->bsd", h, p["wo"]) + p["bo"]


def _group(p, prefix):
    """The leaves under ``prefix/``, by the rest of their path."""
    n = len(prefix) + 1
    return {k[n:]: v for k, v in p.items() if k.startswith(prefix + "/")}


def loss(s: dict, p: dict, batch: dict, *, einsum) -> jax.Array:
    """Mean next-token cross-entropy of one learner's batch."""
    ln = lambda q, x: _ln(x, q["scale"], q["bias"])
    fe1, fe2 = _group(p, "frontend/conv1"), _group(p, "frontend/conv2")
    x = batch["mel"].astype(jnp.float32)
    x = _gelu(_conv(x, fe1["w"], fe1["b"], 1, einsum))
    x = _gelu(_conv(x, fe2["w"], fe2["b"], 2, einsum))
    x = x + _sinusoids(x.shape[1], x.shape[2])

    @jax.checkpoint
    def enc_layer(x, q):
        h = ln(_group(q, "ln1"), x)
        x = x + _attn(_group(q, "attn"), h, h, False, einsum)
        return x + _mlp(_group(q, "mlp"), ln(_group(q, "ln2"), x), einsum)

    x, _ = jax.lax.scan(lambda x, q: (enc_layer(x, q), None), x,
                        _group(p, "enc_layers"))
    enc = ln(_group(p, "enc_norm"), x)

    @jax.checkpoint
    def dec_layer(y, q):
        h = ln(_group(q, "ln1"), y)
        y = y + _attn(_group(q, "self_attn"), h, h, True, einsum)
        y = y + _attn(_group(q, "cross_attn"), ln(_group(q, "lnx"), y), enc,
                      False, einsum)
        return y + _mlp(_group(q, "mlp"), ln(_group(q, "ln2"), y), einsum)

    tokens = batch["tokens"]
    y = p["embed"][tokens] + p["dec_pos"][:tokens.shape[1]]
    y, _ = jax.lax.scan(lambda y, q: (dec_layer(y, q), None), y,
                        _group(p, "dec_layers"))
    y = ln(_group(p, "dec_norm"), y)
    logits = einsum("bsd,vd->bsv", y, p["embed"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def fwd_flops_per_segment(s: dict, frames: int, tokens: int) -> int:
    """Matmul FLOPs of one segment's forward pass: ``frames`` log-mel
    frames (frames / 2 encoder positions), ``tokens`` decoder tokens;
    attention counts the whole score matrix (causal included)."""
    d, ff, V = s["d_model"], s["d_ff"], s["vocab"]
    hE = s["n_heads"] * s["head_dim"]
    S = (frames - 1) // 2 + 1
    T = tokens
    front = 2 * frames * CONV_K * s["n_mels"] * d + 2 * S * CONV_K * d * d
    proj = 2 * d * hE * 2                       # q and out, or k and v
    enc = S * 2 * proj + 2 * 2 * S * S * hE + S * 2 * 2 * d * ff
    dec = (T * 2 * proj + 2 * 2 * T * T * hE                # self
           + T * proj + S * proj + 2 * 2 * T * S * hE       # cross
           + T * 2 * 2 * d * ff)
    head = 2 * T * d * V
    return front + s["n_enc_layers"] * enc + s["n_layers"] * dec + head


def model_flops_per_step(s: dict, traffic: dict) -> float:
    """Forward + backward (3x the forward) over the global batch."""
    segments = traffic["learners"] * traffic["batch_per_learner"]
    return 3.0 * segments * fwd_flops_per_segment(
        s, traffic["frames"], traffic["tokens"])


def audio_seconds_per_step(s: dict, traffic: dict) -> float:
    return (traffic["learners"] * traffic["batch_per_learner"]
            * traffic["frames"] * s["frame_seconds"])


def input_width(s: dict) -> int:
    return s["n_mels"]
