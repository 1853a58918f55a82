"""Model-zoo correctness: smoke per arch family + prefill/decode consistency
+ MoE routing equivalence + sliding-window semantics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_REGISTRY, get_arch
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.sharding import ParamSpec, init_spec_tree

RNG = jax.random.PRNGKey(0)
B, S = 2, 64


def synth_inputs(cfg, model, mode, seq=S):
    shape = ShapeConfig("t", seq, B, mode)
    specs = model.input_specs(shape, mode)

    def mk(ps):
        if ps.dtype == "int32":
            if ps.shape == ():
                return jnp.int32(seq // 2)
            return jax.random.randint(RNG, ps.shape, 0,
                                      min(cfg.vocab, 100), jnp.int32)
        return jax.random.normal(RNG, ps.shape, jnp.float32).astype(ps.dtype)

    return jax.tree.map(mk, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


@pytest.fixture(scope="module")
def zoo():
    out = {}
    for name in sorted(ARCH_REGISTRY):
        cfg = get_arch(name).reduced()
        model = build_model(cfg)
        params = init_spec_tree(model.param_specs(), RNG)
        out[name] = (cfg, model, params)
    return out


# ---------------------------------------------------------------------------
# smoke: every arch trains one step with finite loss (deliverable f)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ARCH_REGISTRY))
def test_arch_smoke_train(zoo, name):
    cfg, model, params = zoo[name]
    batch = synth_inputs(cfg, model, "train")
    loss, g = jax.value_and_grad(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss)), name
    gn = sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
             for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0, name


@pytest.mark.parametrize("name", [n for n in sorted(ARCH_REGISTRY)
                                  if ARCH_REGISTRY[n].family != "lstm"])
def test_arch_smoke_decode_shapes(zoo, name):
    cfg, model, params = zoo[name]
    pb = synth_inputs(cfg, model, "prefill")
    logits, cache = model.prefill_fn(params, pb, cache_len=S)
    assert logits.shape[-1] == cfg.vocab
    tok = jnp.zeros((B, 1), jnp.int32)
    lg, cache2 = model.decode_fn(params, cache, tok, jnp.int32(S // 2))
    assert lg.shape == (B, 1, cfg.vocab)
    assert bool(jnp.isfinite(lg.astype(jnp.float32)).all()), name


# ---------------------------------------------------------------------------
# prefill -> decode == teacher forcing (the serving path is exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["smollm-360m", "granite-moe-3b-a800m",
                                  "mamba2-370m", "hymba-1.5b",
                                  "llama4-scout-17b-a16e"])
def test_prefill_decode_consistency(zoo, name):
    """decode(tokens[:t], then token t) logits == prefill(tokens[:t+1])'s
    last-position logits.

    For capacity-routed MoE the comparison requires no-drop capacity:
    grouped prefill may drop tokens that a solo decode step serves — the
    documented GShard trade-off (see test_moe_capacity_drops_tokens)."""
    cfg, model, params = zoo[name]
    if cfg.moe is not None and cfg.moe.router_impl == "dispatch":
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(
                cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
        model = build_model(cfg)
    T = 32
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, T + 1), 0,
                              cfg.vocab, jnp.int32)
    # ground truth: prefill over t+1 tokens
    full, _ = model.prefill_fn(params, {"tokens": toks}, cache_len=T + 1)
    # serving path: prefill t tokens, decode token t at position t
    part, cache = model.prefill_fn(params, {"tokens": toks[:, :T]},
                                   cache_len=T + 1)
    lg, _ = model.decode_fn(params, cache, toks[:, T:T + 1], jnp.int32(T))
    np.testing.assert_allclose(
        np.asarray(lg[:, 0], np.float32),
        np.asarray(full[:, -1], np.float32), atol=0.11, rtol=0.11)


def test_prefill_decode_consistency_encdec(zoo):
    cfg, model, params = zoo["whisper-large-v3"]
    T = 16
    mel = jax.random.normal(jax.random.PRNGKey(4),
                            (B, 2 * cfg.n_audio_ctx, cfg.n_mels), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, T + 1), 0,
                              cfg.vocab, jnp.int32)
    full, _ = model.prefill_fn(
        params, {"mel": mel, "tokens": toks}, cache_len=T + 1)
    part, cache = model.prefill_fn(
        params, {"mel": mel, "tokens": toks[:, :T]}, cache_len=T + 1)
    lg, _ = model.decode_fn(params, cache, toks[:, T:T + 1], jnp.int32(T))
    np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                               np.asarray(full[:, -1], np.float32),
                               atol=0.11, rtol=0.11)


def test_multistep_decode_matches_teacher_forcing(zoo):
    cfg, model, params = zoo["smollm-360m"]
    T, extra = 24, 4
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, T + extra), 0,
                              cfg.vocab, jnp.int32)
    full, _ = model.prefill_fn(params, {"tokens": toks},
                               cache_len=T + extra)
    _, cache = model.prefill_fn(params, {"tokens": toks[:, :T]},
                                cache_len=T + extra)
    for i in range(extra):
        lg, cache = model.decode_fn(params, cache, toks[:, T + i:T + i + 1],
                                    jnp.int32(T + i))
    np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                               np.asarray(full[:, -1], np.float32),
                               atol=0.15, rtol=0.15)


# ---------------------------------------------------------------------------
# MoE: dispatch (capacity) routing == dense routing when nothing drops
# ---------------------------------------------------------------------------

def test_moe_dispatch_matches_dense_at_high_capacity():
    from repro.models.moe import moe_apply, moe_param_specs

    cfg = get_arch("granite-moe-3b-a800m").reduced()
    cfg_disp = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, router_impl="dispatch",
                                     capacity_factor=float(cfg.moe.num_experts)))
    cfg_dense = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, router_impl="dense"))
    p = init_spec_tree(moe_param_specs(cfg), jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    y1, aux1 = moe_apply(cfg_disp, p, x)
    y2, aux2 = moe_apply(cfg_dense, p, x)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=0.06,
                               rtol=0.06)
    np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-4)


def test_moe_capacity_drops_tokens():
    """At tiny capacity the dispatch path must differ (tokens dropped) but
    stay finite — the documented GShard behaviour."""
    from repro.models.moe import moe_apply, moe_param_specs

    cfg = get_arch("granite-moe-3b-a800m").reduced()
    cfg_tiny = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, router_impl="dispatch",
                                     capacity_factor=0.25))
    p = init_spec_tree(moe_param_specs(cfg), jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    y, aux = moe_apply(cfg_tiny, p, x)
    assert bool(jnp.isfinite(y.astype(jnp.float32)).all())


# ---------------------------------------------------------------------------
# sliding windows
# ---------------------------------------------------------------------------

def test_window_masks_attention():
    from repro.kernels.ref import attention_ref
    from repro.models.attention import attn_seq

    q = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 2, 16))
    out = attn_seq(q, k, v, causal=True, window=jnp.int32(16), q_chunk=32)
    expect = attention_ref(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_attn_seq_takes_a_shorter_last_chunk(causal):
    """75 positions in chunks of 32 leave a last chunk of 11, as whisper's
    1500-frame encoder leaves one of 476 after two of 512: the output and
    its gradients equal the unchunked softmax attention's (float32, so to
    float32 rounding)."""
    from repro.kernels.ref import attention_ref
    from repro.models.attention import attn_seq

    q = jax.random.normal(jax.random.PRNGKey(1), (2, 75, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 75, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 75, 2, 16))
    out, grads = jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(attn_seq(*a, causal=causal, q_chunk=32))),
        argnums=(0, 1, 2))(q, k, v)
    want, want_g = jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(attention_ref(*a, causal=causal))),
        argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(out), float(want), rtol=1e-5)
    for g, w in zip(grads, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=1e-4)


def test_gelu_is_the_exact_erf_form():
    """``act="gelu"`` (whisper's FFN and front end) is x * Phi(x) through
    erf, to float32 rounding, not the tanh approximation, which is up to
    5e-4 away on this range."""
    from jax.scipy.special import erf

    from repro.models.common import gelu

    x = jnp.linspace(-6.0, 6.0, 4001, dtype=jnp.float32)
    exact = 0.5 * x * (1.0 + erf(x / np.sqrt(2.0)))
    np.testing.assert_allclose(np.asarray(gelu(x)), np.asarray(exact),
                               atol=2e-6)
    assert float(jnp.max(jnp.abs(jax.nn.gelu(x, approximate=True)
                                 - exact))) > 1e-4


def _tiny_whisper():
    """2 + 2 layers at d_model 64 with 520 encoder positions: the query
    chunks of 512 leave a last one of 8."""
    cfg = dataclasses.replace(
        get_arch("whisper-large-v3").reduced(), n_layers=2, n_enc_layers=2,
        d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
        vocab=96, n_mels=8, n_audio_ctx=520, n_text_ctx=16)
    model = build_model(cfg)
    params = init_spec_tree(model.param_specs(), RNG)
    batch = {"mel": jax.random.normal(RNG, (B, 1040, 8), jnp.float32),
             "tokens": jnp.zeros((B, 12), jnp.int32),
             "labels": jnp.ones((B, 12), jnp.int32)}
    return model, params, batch


def test_whisper_conv_front_end_matches_lax_conv():
    """The front end's convolutions (kernel 3, padding 1; stride 1, then
    2) equal lax.conv_general_dilated in float32."""
    from repro.models.encdec import conv1d

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 41, 8))
    for stride, c_in in ((1, 8), (2, 8)):
        p = {"w": jax.random.normal(jax.random.PRNGKey(stride), (3, c_in, 6)),
             "b": jax.random.normal(jax.random.PRNGKey(9), (6,))}
        got = conv1d(p, x, stride)
        want = jax.lax.conv_general_dilated(
            x, p["w"], (stride,), [(1, 1)],
            dimension_numbers=("NWC", "WIO", "NWC"),
            precision=jax.lax.Precision.HIGHEST) + p["b"]
        assert got.shape == (2, (41 - 1) // stride + 1, 6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_whisper_step_names_its_layers_and_counts_attention():
    """The compiled training step carries the scopes frontend, encoder,
    decoder, self_attn, cross_attn and out_head in its op metadata, and
    the ``models/attn_seq`` counter counts one trace of each attention:
    the encoder's with a shorter last chunk, the decoder's causal self-
    and plain cross-attention."""
    import re

    from repro import obs

    model, params, batch = _tiny_whisper()
    obs.configure()
    try:
        hlo = jax.jit(jax.value_and_grad(model.loss_fn)).lower(
            params, batch).compile().as_text()
        counts = {(r["tags"]["ragged"], r["tags"]["causal"]): r["value"]
                  for r in obs.get_metrics().snapshot()
                  if r["name"] == "models/attn_seq"}
    finally:
        obs.reset()
    assert counts == {(1, 0): 1, (0, 1): 1, (0, 0): 1}
    for scope in ("frontend", "encoder", "decoder", "self_attn",
                  "cross_attn", "out_head"):
        assert re.search(rf'op_name="[^"]*[/(]{scope}[)/]', hlo), scope


def _whisper_grads(model, params, batch, *, remat=True, policy=None):
    """The tiny whisper's loss and gradients, its layers rematerialised
    with ``policy`` in place of ``encdec.LAYER_POLICY``, or not at all."""
    from repro.models import encdec

    saved = encdec.LAYER_POLICY
    encdec.LAYER_POLICY = policy or saved
    try:
        m = build_model(dataclasses.replace(model.cfg, remat=remat))
        return jax.jit(jax.value_and_grad(m.loss_fn))(params, batch)
    finally:
        encdec.LAYER_POLICY = saved


@pytest.mark.parametrize("want,got,rel", [
    ("nothing_saveable", "policy", 0.0),
    ("no_remat", "policy", 3e-2),
    ("no_remat", "nothing_saveable", 3e-2)])
def test_whisper_remat_policy_keeps_loss_and_gradients(want, got, rel):
    """The layer policy (keep the projections and attention outputs)
    gives the loss and every gradient leaf of a plain checkpoint that
    keeps nothing, to float32 rounding.  Without any checkpoint the loss
    is the same and the gradients differ by the bfloat16 rounding of the
    backward's sums, whose order the checkpoint changes: 1.1e-2 of a
    leaf's norm at most here, and as large for the plain checkpoint as
    for the policy."""
    model, params, batch = _tiny_whisper()
    runs = {
        "policy": lambda: _whisper_grads(model, params, batch),
        "nothing_saveable": lambda: _whisper_grads(
            model, params, batch,
            policy=jax.checkpoint_policies.nothing_saveable),
        "no_remat": lambda: _whisper_grads(model, params, batch,
                                           remat=False)}
    (lw, gw), (lg, gg) = runs[want](), runs[got]()
    np.testing.assert_allclose(float(lg), float(lw), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gw), jax.tree.leaves(gg)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if rel:
            assert np.linalg.norm(b - a) <= rel * np.linalg.norm(a)
        else:
            np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-7)


def test_whisper_remat_saves_projections_and_outputs_not_scores():
    """What the layer policy keeps for the backward (2 encoder layers, 3
    decoder layers, so the stacks tell apart): no attention score block
    of any query chunk; the attention outputs (one per encoder layer,
    two per decoder layer: the counts drop by that much when the policy
    leaves ``attn_out`` out) and the q/k/v projections, among them the
    cross-attention's k and v over the encoder output."""
    import collections

    from jax._src.ad_checkpoint import saved_residuals

    from repro.models import encdec

    model, _, batch = _tiny_whisper()
    model = build_model(dataclasses.replace(model.cfg, n_layers=3))
    params = init_spec_tree(model.param_specs(), RNG)
    names = jax.checkpoint_policies.save_only_these_names

    def residuals(policy):
        saved = encdec.LAYER_POLICY
        encdec.LAYER_POLICY = policy
        try:
            res = saved_residuals(model.loss_fn, params, batch)
        finally:
            encdec.LAYER_POLICY = saved
        return collections.Counter(tuple(a.shape) for a, _ in res)

    got = residuals(encdec.LAYER_POLICY)
    # scores (B, heads, 1, q_chunk, S_k): encoder chunks of 512 and 8 over
    # 520 keys, decoder 12 queries over 12 and 520 keys
    scores = {(512, 520), (8, 520), (12, 12), (12, 520)}
    assert not [s for s in got if s[-2:] in scores]
    enc, dec, cross = (2, B, 520, 4, 16), (3, B, 12, 4, 16), (3, B, 520, 4, 16)
    no_out = residuals(names(encdec.ATTN_QKV, encdec.ATTN_PROJ))
    assert (got[enc] - no_out[enc], got[dec] - no_out[dec]) == (1, 2)
    no_qkv = residuals(names(encdec.ATTN_OUT, encdec.ATTN_PROJ))
    assert got[enc] - no_qkv[enc] >= 3        # q, k, v
    assert got[dec] - no_qkv[dec] >= 4        # self q, k, v; cross q
    assert got[cross] - no_qkv[cross] == 2    # cross k, v


@pytest.mark.parametrize("remat,policy", [(True, "dots+attn_out"),
                                          (False, "none")])
def test_whisper_remat_counter_counts_each_stack(remat, policy):
    """The ``models/remat`` counter counts one trace of each stack, with
    the policy the configuration asks for."""
    from repro import obs

    model, params, batch = _tiny_whisper()
    model = build_model(dataclasses.replace(model.cfg, remat=remat))
    obs.configure()
    try:
        jax.jit(jax.value_and_grad(model.loss_fn)).lower(params, batch)
        counts = {(r["tags"]["stack"], r["tags"]["policy"]): r["value"]
                  for r in obs.get_metrics().snapshot()
                  if r["name"] == "models/remat"}
    finally:
        obs.reset()
    assert counts == {("enc", policy): 1, ("dec", policy): 1}


def test_layer_windows_global_override():
    from repro.models.transformer import layer_windows, GLOBAL_WINDOW

    cfg = get_arch("hymba-1.5b")
    ws = layer_windows(cfg, 1 << 16)
    assert ws[0] == GLOBAL_WINDOW and ws[15] == GLOBAL_WINDOW \
        and ws[31] == GLOBAL_WINDOW
    assert ws[1] == cfg.window


def test_long_context_variant_uses_window_for_long():
    from repro.models.transformer import layer_windows, GLOBAL_WINDOW

    cfg = get_arch("phi3-medium-14b")
    assert layer_windows(cfg, 1 << 16)[0] == GLOBAL_WINDOW
    assert layer_windows(cfg, 1 << 16, long_context=True)[0] == \
        cfg.window_for_long
