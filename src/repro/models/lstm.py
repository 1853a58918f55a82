"""The paper's acoustic model: 6-layer bi-directional LSTM DNN-HMM with a
linear bottleneck and a 32,000-way CD-HMM-state softmax (Cui et al. §V).

The LSTM cell is the compute hot-spot the Pallas kernel in
``repro.kernels.lstm_cell`` fuses (gate matmuls + elementwise); this module
doubles as its pure-jnp oracle through ``repro.kernels.ref``.

Variable-length utterances (the ``lengths`` batch contract)
-----------------------------------------------------------
``forward``/``loss_train`` accept right-padded batches with a per-row
valid-length vector ``lengths`` (B,) — the contract emitted by
``repro.data.pipeline`` with ``var_len=True``.  Masking semantics, shared
bit-for-bit by the jax scan and the Pallas kernels:

* on padded steps (t >= lengths[b]) the recurrent (h, c) carry is FROZEN
  (not updated), so padded frames cannot enter any weight gradient;
* the layer output at padded frames is 0, so the next layer sees zeroed
  padding exactly like the input layer did;
* the backward direction therefore reverses *within* each utterance's
  valid span: right-padding means its leading invalid segment carries the
  zero initial state untouched until the last valid frame;
* the loss is normalized by the number of valid frames, not B*T.

When ``lengths`` is None every path reduces to the rectangular behavior.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import cross_entropy, sequence_mask
from repro.sharding import ParamSpec


def lstm_cell_step(wx, wh, b, x_t, h, c):
    """One LSTM step.  x_t: (B,D_in); h/c: (B,H).  Gate order: i,f,g,o."""
    gates = (jnp.einsum("bd,dg->bg", x_t, wx)
             + jnp.einsum("bh,hg->bg", h, wh)).astype(jnp.float32) + b
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return h.astype(x_t.dtype), c


def _kernel_knobs(cfg):
    """(block_b, vmem_budget, stash_dtype, seq_chunk) for the Pallas LSTM
    kernels (seq_chunk: 0 = per-step stash, -1 = auto-tuned chunk length,
    K > 0 = K-frame chunked recompute; docs/kernels.md)."""
    block_b = getattr(cfg, "lstm_block_b", 0) or None
    budget_mb = getattr(cfg, "lstm_vmem_budget_mb", 0)
    stash = getattr(cfg, "lstm_stash_dtype", "float32") or "float32"
    seq_chunk = getattr(cfg, "lstm_seq_chunk", 0) or 0
    return (block_b, (budget_mb * 2 ** 20 if budget_mb else None), stash,
            seq_chunk)


def lstm_layer(p, x, *, lengths=None, reverse: bool = False,
               kernel_impl: str = "jax", block_b: int = None,
               vmem_budget: int = None, stash_dtype: str = None,
               seq_chunk: int = 0):
    """x: (B,T,D_in) -> (B,T,H).

    ``lengths`` (B,) int enables the masked recurrence (carry frozen and
    output zeroed at t >= lengths[b]; see module docstring)."""
    B, T, _ = x.shape
    H = p["wh"].shape[0]
    h0 = jnp.zeros((B, H), x.dtype)
    c0 = jnp.zeros((B, H), jnp.float32)

    if kernel_impl == "pallas":
        from repro.kernels.ops import lstm_sequence
        return lstm_sequence(p["wx"], p["wh"], p["b"], x, lengths,
                             reverse=reverse, block_b=block_b,
                             vmem_budget=vmem_budget,
                             stash_dtype=stash_dtype,
                             seq_chunk=seq_chunk)

    if lengths is None:
        def step(carry, x_t):
            h, c = carry
            h, c = lstm_cell_step(p["wx"], p["wh"], p["b"], x_t, h, c)
            return (h, c), h

        xs = jnp.moveaxis(x, 1, 0)
        (_, _), hs = jax.lax.scan(step, (h0, c0), xs, reverse=reverse)
        return jnp.moveaxis(hs, 0, 1)

    def step(carry, inp):
        x_t, t = inp
        h, c = carry
        h2, c2 = lstm_cell_step(p["wx"], p["wh"], p["b"], x_t, h, c)
        v = (t < lengths)[:, None]
        h = jnp.where(v, h2, h)                       # freeze the carry
        c = jnp.where(v, c2, c)
        return (h, c), jnp.where(v, h2, jnp.zeros_like(h2))

    xs = jnp.moveaxis(x, 1, 0)
    (_, _), hs = jax.lax.scan(step, (h0, c0), (xs, jnp.arange(T)),
                              reverse=reverse)
    return jnp.moveaxis(hs, 0, 1)


def layer_specs(d_in: int, hidden: int, dtype: str):
    return {
        "fwd": {
            "wx": ParamSpec((d_in, 4 * hidden), dtype,
                            ("feature", "lstm_gates"), "lecun"),
            "wh": ParamSpec((hidden, 4 * hidden), dtype,
                            ("lstm_hidden", "lstm_gates"), "lecun"),
            "b": ParamSpec((4 * hidden,), "float32", ("lstm_gates",), "zeros"),
        },
        "bwd": {
            "wx": ParamSpec((d_in, 4 * hidden), dtype,
                            ("feature", "lstm_gates"), "lecun"),
            "wh": ParamSpec((hidden, 4 * hidden), dtype,
                            ("lstm_hidden", "lstm_gates"), "lecun"),
            "b": ParamSpec((4 * hidden,), "float32", ("lstm_gates",), "zeros"),
        },
    }


def param_specs(cfg):
    H = cfg.lstm_hidden
    dt = cfg.param_dtype
    layers = {}
    d_in = cfg.input_dim
    for i in range(cfg.n_layers):
        layers[f"layer_{i}"] = layer_specs(d_in, H, dt)
        d_in = 2 * H
    return {
        "layers": layers,
        "bottleneck": ParamSpec((2 * H, cfg.lstm_bottleneck), dt,
                                ("lstm_hidden", "bottleneck"), "lecun"),
        "softmax_w": ParamSpec((cfg.lstm_bottleneck, cfg.vocab), dt,
                               ("bottleneck", "vocab"), "normal", 0.02),
        "softmax_b": ParamSpec((cfg.vocab,), "float32", ("vocab",), "zeros"),
    }


def hidden(cfg, params, features, lengths=None, *,
           kernel_impl: str = "jax"):
    """features: (B, T, input_dim) -> bottleneck output z (B, T, K): the
    BLSTM stack and the linear bottleneck, everything before the output
    layer.

    The pallas path runs the WHOLE bi-LSTM stack as one fused kernel
    invocation (``repro.kernels.lstm_cell.blstm_stack_sequence``):
    inter-layer activations stay VMEM-resident on the inference call, and
    under ``jax.value_and_grad`` its custom VJP falls back to the
    per-layer stashing forward/backward (honoring the ``lstm_stash_dtype``
    / ``lstm_seq_chunk`` config knobs).  It is called inside the caller's
    trace, not through a jit wrapper: a nested jit would trace the fused
    inference primal even under differentiation, and print its path
    marker for a kernel the training step never runs.

    ``lengths`` (B,) int threads the masked recurrence through every
    layer (frozen carries + zeroed padded outputs; module docstring).

    Each part runs under a ``jax.named_scope`` (``blstm_l{i}``,
    ``bottleneck``), which lands in the op metadata of the compiled step
    (docs/observability.md)."""
    x = features.astype(jnp.bfloat16)
    block_b, vmem_budget, stash_dtype, seq_chunk = _kernel_knobs(cfg)
    if kernel_impl == "pallas":
        # the stack kernel's training rules scope each layer blstm_l{i}
        from repro.kernels.lstm_cell import blstm_stack_sequence
        layers = tuple(
            (p["fwd"]["wx"], p["fwd"]["wh"], p["fwd"]["b"],
             p["bwd"]["wx"], p["bwd"]["wh"], p["bwd"]["b"])
            for p in (params["layers"][f"layer_{i}"]
                      for i in range(cfg.n_layers)))
        x = blstm_stack_sequence(layers, x, lengths, block_b=block_b,
                                 vmem_budget=vmem_budget,
                                 stash_dtype=stash_dtype,
                                 seq_chunk=seq_chunk)
    else:
        for i in range(cfg.n_layers):
            p = params["layers"][f"layer_{i}"]
            with jax.named_scope(f"blstm_l{i}"):
                fwd = lstm_layer(p["fwd"], x, lengths=lengths,
                                 kernel_impl=kernel_impl)
                bwd = lstm_layer(p["bwd"], x, lengths=lengths,
                                 reverse=True, kernel_impl=kernel_impl)
                x = jnp.concatenate([fwd, bwd], axis=-1)
    with jax.named_scope("bottleneck"):
        return jnp.einsum("btd,dk->btk", x, params["bottleneck"])


def forward(cfg, params, features, lengths=None, *,
            kernel_impl: str = "jax"):
    """features: (B, T, input_dim) -> logits (B, T, vocab): :func:`hidden`
    and the output layer (scope ``softmax_ce``), for evaluation, serving
    and decoding."""
    z = hidden(cfg, params, features, lengths, kernel_impl=kernel_impl)
    with jax.named_scope("softmax_ce"):
        return (jnp.einsum("btk,kv->btv", z, params["softmax_w"])
                .astype(jnp.float32) + params["softmax_b"])


def loss_train(cfg, params, batch, *, kernel_impl: str = "jax"):
    """Frame-level CE.  If the batch carries ``lengths``, padded frames are
    excluded and the loss normalizes by the valid-frame count (the masked
    contract of ``repro.data.pipeline``).

    Under ``kernel_impl="pallas"``, with a vocabulary the kernel tiles (a
    multiple of 128), the output layer, softmax and CE gradient run as
    one Pallas kernel (``repro.kernels.softmax_ce``) that never writes
    the logits out; otherwise the logits of :func:`forward` go through
    ``cross_entropy``."""
    lengths, labels = batch.get("lengths"), batch["labels"]
    fused = False
    if kernel_impl == "pallas":
        from repro.kernels import softmax_ce as SCE
        fused = SCE.supported(cfg.vocab)
    out = (hidden if fused else forward)(cfg, params, batch["features"],
                                         lengths, kernel_impl=kernel_impl)
    with jax.named_scope("softmax_ce"):
        mask = (None if lengths is None
                else sequence_mask(lengths, labels.shape[1]))
        if fused:
            return SCE.softmax_ce(out, params["softmax_w"],
                                  params["softmax_b"], labels, mask)
        return cross_entropy(out, labels, mask=mask)
