"""Per-kernel allclose vs the pure-jnp oracles (interpret mode on CPU),
with shape/dtype sweeps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lstm_cell import blstm_sequence, lstm_sequence
from repro.kernels.ssd_scan import ssd
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(42)


def _mk(shape, dtype, i=0, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, i), shape,
                              jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("B,S,H,KV,E", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 6, 2, 64),      # GQA 3:1
    (1, 256, 8, 1, 128),     # MQA, 128 head_dim
])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention(B, S, H, KV, E, dtype, window):
    q = _mk((B, S, H, E), dtype, 1)
    k = _mk((B, S, KV, E), dtype, 2)
    v = _mk((B, S, KV, E), dtype, 3)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(np.float32),
                               expect.astype(np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_noncausal():
    q = _mk((2, 128, 4, 64), jnp.bfloat16, 4)
    k = _mk((2, 128, 4, 64), jnp.bfloat16, 5)
    v = _mk((2, 128, 4, 64), jnp.bfloat16, 6)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    expect = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out.astype(np.float32),
                               expect.astype(np.float32), atol=2e-2,
                               rtol=2e-2)


def test_flash_attention_matches_model_chunked_path():
    """The pure-JAX attn_seq (model path) and the kernel agree."""
    from repro.models.attention import attn_seq

    q = _mk((1, 256, 4, 64), jnp.bfloat16, 7)
    k = _mk((1, 256, 2, 64), jnp.bfloat16, 8)
    v = _mk((1, 256, 2, 64), jnp.bfloat16, 9)
    a = attn_seq(q, k, v, causal=True, q_chunk=64)
    b = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                        interpret=True)
    np.testing.assert_allclose(a.astype(np.float32), b.astype(np.float32),
                               atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# fused LSTM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("B,T,D,H", [(4, 21, 26, 32), (2, 33, 16, 16)])
@pytest.mark.parametrize("reverse", [False, True])
def test_lstm_sequence(B, T, D, H, dtype, reverse):
    wx = _mk((D, 4 * H), dtype, 10, 0.3)
    wh = _mk((H, 4 * H), dtype, 11, 0.3)
    b = _mk((4 * H,), jnp.float32, 12, 0.1)
    x = _mk((B, T, D), dtype, 13)
    out = lstm_sequence(wx, wh, b, x, reverse=reverse, interpret=True)
    expect = ref.lstm_ref(wx, wh, b, x, reverse=reverse)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(out.astype(np.float32),
                               expect.astype(np.float32), atol=tol, rtol=tol)


def _norm_close(got, want, tol, name=""):
    """allclose after normalizing by the oracle's scale (grad tensors span
    orders of magnitude; raw atol would be meaningless)."""
    scale = float(jnp.abs(want.astype(jnp.float32)).max()) + 1e-8
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=tol, err_msg=name)


def _mk_lstm(D, H, dtype, base):
    wx = _mk((D, 4 * H), dtype, base, 0.3)
    wh = _mk((H, 4 * H), dtype, base + 1, 0.3)
    b = _mk((4 * H,), jnp.float32, base + 2, 0.1)
    return wx, wh, b


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("B,T,D,H,block_b", [
    (4, 9, 12, 16, None),     # single tile
    (5, 6, 8, 16, 2),         # tiled, B not a multiple of block_b (padding)
])
def test_lstm_sequence_grad(B, T, D, H, block_b, reverse, dtype):
    """value_and_grad parity of the Pallas custom VJP vs jax autodiff
    through the scan oracle, for all four inputs."""
    wx, wh, b = _mk_lstm(D, H, dtype, 70)
    x = _mk((B, T, D), dtype, 73)

    def loss_k(wx, wh, b, x):
        y = lstm_sequence(wx, wh, b, x, reverse=reverse, interpret=True,
                          block_b=block_b)
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    def loss_r(wx, wh, b, x):
        y = ref.lstm_ref(wx, wh, b, x, reverse=reverse)
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    v_k, g_k = jax.value_and_grad(loss_k, argnums=(0, 1, 2, 3))(wx, wh, b, x)
    v_r, g_r = jax.value_and_grad(loss_r, argnums=(0, 1, 2, 3))(wx, wh, b, x)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(float(v_k), float(v_r), rtol=tol)
    for got, want, name in zip(g_k, g_r, ("dwx", "dwh", "db", "dx")):
        assert got.dtype == want.dtype
        _norm_close(got, want, tol, name)


def test_blstm_fused_bitidentical_and_tiled():
    """The fused bidirectional kernel is bit-identical to two separate
    direction passes, and batch tiling (incl. a non-dividing block_b)
    is bit-identical to the untiled kernel."""
    B, T, D, H = 5, 7, 12, 16
    wxf, whf, bf = _mk_lstm(D, H, jnp.bfloat16, 80)
    wxb, whb, bb = _mk_lstm(D, H, jnp.bfloat16, 84)
    x = _mk((B, T, D), jnp.bfloat16, 88)

    fused = blstm_sequence(wxf, whf, bf, wxb, whb, bb, x, interpret=True,
                           block_b=8)
    sep = jnp.concatenate(
        [lstm_sequence(wxf, whf, bf, x, interpret=True, block_b=8),
         lstm_sequence(wxb, whb, bb, x, reverse=True, interpret=True,
                       block_b=8)], axis=-1)
    np.testing.assert_array_equal(np.asarray(fused, np.float32),
                                  np.asarray(sep, np.float32))

    tiled = blstm_sequence(wxf, whf, bf, wxb, whb, bb, x, interpret=True,
                           block_b=2)   # 5 % 2 != 0 -> zero-pad path
    np.testing.assert_array_equal(np.asarray(fused, np.float32),
                                  np.asarray(tiled, np.float32))
    _norm_close(fused, ref.blstm_ref(wxf, whf, bf, wxb, whb, bb, x), 2e-2)


@pytest.mark.parametrize("block_b", [None, 2])
def test_blstm_grad(block_b):
    B, T, D, H = 4, 6, 8, 16
    wxf, whf, bf = _mk_lstm(D, H, jnp.bfloat16, 90)
    wxb, whb, bb = _mk_lstm(D, H, jnp.bfloat16, 94)
    x = _mk((B, T, D), jnp.bfloat16, 98)

    def loss_k(*w):
        y = blstm_sequence(*w, interpret=True, block_b=block_b)
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    def loss_r(*w):
        return jnp.mean(jnp.square(
            ref.blstm_ref(*w).astype(jnp.float32)))

    args = (wxf, whf, bf, wxb, whb, bb, x)
    v_k, g_k = jax.value_and_grad(loss_k, argnums=tuple(range(7)))(*args)
    v_r, g_r = jax.value_and_grad(loss_r, argnums=tuple(range(7)))(*args)
    np.testing.assert_allclose(float(v_k), float(v_r), rtol=2e-2)
    names = ("dwxf", "dwhf", "dbf", "dwxb", "dwhb", "dbb", "dx")
    for got, want, name in zip(g_k, g_r, names):
        assert got.dtype == want.dtype
        _norm_close(got, want, 2e-2, name)


@pytest.mark.parametrize("reverse", [False, True])
def test_lstm_layer_pallas_matches_jax(reverse):
    """models/lstm.lstm_layer's per-direction pallas path (incl. the
    block_b/vmem_budget plumbing) tracks its own jax scan path."""
    from repro.models.lstm import lstm_layer

    D, H = 12, 16
    wx, wh, b = _mk_lstm(D, H, jnp.bfloat16, 104)
    p = {"wx": wx, "wh": wh, "b": b}
    x = _mk((5, 6, D), jnp.bfloat16, 108)
    got = lstm_layer(p, x, reverse=reverse, kernel_impl="pallas", block_b=2)
    want = lstm_layer(p, x, reverse=reverse, kernel_impl="jax")
    _norm_close(got, want, 2e-2)


def test_lstm_pallas_loss_train_and_ad_psgd_step():
    """End-to-end acceptance: jax.value_and_grad through
    models/lstm.loss_train(kernel_impl='pallas') matches the jax path,
    and a replicated ad_psgd train step runs on the pallas kernel."""
    import dataclasses

    from repro.configs import get_arch
    from repro.core import strategies as ST
    from repro.models import build_model
    from repro.optim.optimizers import get_optimizer
    from repro.optim.schedules import constant
    from repro.sharding import init_spec_tree

    cfg = dataclasses.replace(get_arch("swb2000-blstm").reduced(),
                              n_layers=1, lstm_hidden=16, lstm_bottleneck=8,
                              input_dim=12, vocab=32, lstm_block_b=2)
    model = build_model(cfg)
    params = init_spec_tree(model.param_specs(), jax.random.PRNGKey(0))
    B, T = 4, 5
    batch = {
        "features": np.asarray(_mk((B, T, cfg.input_dim), jnp.float32, 100)),
        "labels": np.asarray(
            jax.random.randint(KEY, (B, T), 0, cfg.vocab, jnp.int32)),
    }

    v_j, g_j = jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, kernel_impl="jax"))(params)
    v_p, g_p = jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, kernel_impl="pallas"))(params)
    np.testing.assert_allclose(float(v_p), float(v_j), rtol=2e-2)
    flat_j, _ = jax.tree.flatten(g_j)
    flat_p, treedef = jax.tree.flatten(g_p)
    for got, want in zip(flat_p, flat_j):
        _norm_close(got, want, 2e-2, str(treedef))

    strategy = ST.get_strategy("ad_psgd")
    opt = get_optimizer("sgd")
    step = ST.make_train_step(
        strategy,
        lambda p, bt: model.loss_fn(p, bt, kernel_impl="pallas"),
        opt, constant(0.05), n_learners=2)
    state = ST.init_state(strategy, ST.stack_for_learners(params, 2), opt)
    jit_step = jax.jit(step)
    for _ in range(2):
        state, metrics = jit_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# fused output layer + softmax cross-entropy
# ---------------------------------------------------------------------------

def _ce_ref(z, w, b, labels, mask):
    """The oracle: ``cross_entropy`` of ``z @ w + b`` with f32 logits."""
    from repro.models.common import cross_entropy
    return cross_entropy(jnp.dot(z, w, preferred_element_type=jnp.float32)
                         + b, labels, mask=mask)


@pytest.mark.parametrize("rows,V,masked,learners,primal", [
    (200, 384, False, 0, False),     # rows off the 128-row tile
    (200, 640, True, 0, False),
    (96, 384, True, 3, False),       # vmap over learners, masked
    (300, 640, False, 3, False),     # vmap, three row tiles
    (200, 640, True, 0, True),       # the primal-only (no-grad) call
    (96, 384, False, 3, True),
])
def test_softmax_ce_matches_cross_entropy(rows, V, masked, learners, primal):
    """The fused output-layer kernel's loss and dz, dW, db against
    value_and_grad of cross_entropy(z @ W + b)."""
    from repro.kernels.softmax_ce import softmax_ce

    K = 32
    lead = (learners,) if learners else ()
    z = _mk(lead + (rows, K), jnp.bfloat16, 200)
    w = _mk(lead + (K, V), jnp.bfloat16, 201, 0.3)
    b = _mk(lead + (V,), jnp.float32, 202, 0.1)
    labels = jax.random.randint(jax.random.fold_in(KEY, 203), lead + (rows,),
                                0, V, jnp.int32)
    mask = (jnp.arange(rows) < rows - 37) if masked else None

    def per_learner(f):
        return jax.vmap(f) if learners else f

    if primal:
        got = per_learner(lambda *a: softmax_ce(*a, mask, interpret=True))(
            z, w, b, labels)
        want = per_learner(lambda *a: _ce_ref(*a, mask))(z, w, b, labels)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        return
    vg = functools.partial(jax.value_and_grad, argnums=(0, 1, 2))
    v_k, g_k = per_learner(vg(
        lambda z, w, b, l: softmax_ce(z, w, b, l, mask, interpret=True)))(
            z, w, b, labels)
    v_r, g_r = per_learner(vg(
        lambda z, w, b, l: _ce_ref(z, w, b, l, mask)))(z, w, b, labels)
    np.testing.assert_allclose(v_k, v_r, rtol=1e-5)
    for got, want, name in zip(g_k, g_r, ("dz", "dW", "db")):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        # dz and dW take the logits' gradient as a bf16 MXU operand
        _norm_close(got, want, 1e-5 if name == "db" else 1e-2, name)
    if masked:                          # padded frames reach no gradient
        assert not np.any(np.asarray(g_k[0], np.float32)[..., rows - 37:, :])


def _tiny_blstm(vocab):
    import dataclasses

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.sharding import init_spec_tree

    cfg = dataclasses.replace(get_arch("swb2000-blstm").reduced(),
                              n_layers=1, lstm_hidden=16, lstm_bottleneck=16,
                              input_dim=12, vocab=vocab, lstm_block_b=2)
    model = build_model(cfg)
    params = init_spec_tree(model.param_specs(), jax.random.PRNGKey(0))
    B, T = 4, 5
    batch = {
        "features": np.asarray(_mk((B, T, cfg.input_dim), jnp.float32, 204)),
        "labels": np.asarray(jax.random.randint(
            jax.random.fold_in(KEY, 205), (B, T), 0, vocab, jnp.int32)),
        "lengths": np.asarray([5, 3, 4, 5], np.int32),
    }
    return model, params, batch


def test_loss_train_fused_output_layer_matches_jax():
    """models/lstm.loss_train(kernel_impl='pallas') with a vocabulary the
    fused output-layer kernel takes: loss and every gradient track the
    jax path (which rounds the logits to bf16 before the bias add)."""
    model, params, batch = _tiny_blstm(256)
    v_j, g_j = jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, kernel_impl="jax"))(params)
    v_p, g_p = jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, kernel_impl="pallas"))(params)
    np.testing.assert_allclose(float(v_p), float(v_j), rtol=2e-3)
    flat_j = jax.tree.leaves_with_path(g_j)
    for (path, want), got in zip(flat_j, jax.tree.leaves(g_p)):
        _norm_close(got, want, 2e-2, jax.tree_util.keystr(path))


def test_softmax_ce_counter_counts_the_fused_path_only():
    """``kernels/softmax_ce`` counts each trace of the fused path, by
    rule: the training rule under value_and_grad, the primal rule for a
    loss alone; the jax path and a vocabulary off the 128 lanes leave it
    alone."""
    from repro import obs

    def counts():
        return {r["tags"]["rule"]: r["value"]
                for r in obs.get_metrics().snapshot()
                if r["name"] == "kernels/softmax_ce"}

    model, params, batch = _tiny_blstm(256)
    obs.configure()
    try:
        jax.value_and_grad(
            lambda p: model.loss_fn(p, batch, kernel_impl="jax"))(params)
        assert counts() == {}
        jax.value_and_grad(
            lambda p: model.loss_fn(p, batch, kernel_impl="pallas"))(params)
        assert counts() == {"train": 1}
        model.loss_fn(params, batch, kernel_impl="pallas")
        assert counts() == {"train": 1, "primal": 1}
        model, params, batch = _tiny_blstm(200)
        model.loss_fn(params, batch, kernel_impl="pallas")
        assert counts() == {"train": 1, "primal": 1}
    finally:
        obs.reset()


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 4, 16, 8, 32),
    (1, 64, 2, 32, 16, 16),
    (1, 256, 8, 64, 64, 64),   # production-like head/state dims
])
def test_ssd_kernel(B, S, H, P, N, chunk):
    x = _mk((B, S, H, P), jnp.bfloat16, 20)
    dt = jax.nn.softplus(_mk((B, S, H), jnp.float32, 21))
    A = -jnp.exp(_mk((H,), jnp.float32, 22, 0.5))
    Bm = _mk((B, S, H, N), jnp.bfloat16, 23)
    Cm = _mk((B, S, H, N), jnp.bfloat16, 24)
    y, hf = ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    y_ref, hf_ref = ref.ssd_ref(x, dt, A, Bm, Cm)
    scale = float(jnp.abs(y_ref.astype(jnp.float32)).max()) + 1e-6
    np.testing.assert_allclose(y.astype(np.float32) / scale,
                               y_ref.astype(np.float32) / scale,
                               atol=5e-3)
    hs = float(jnp.abs(hf_ref).max()) + 1e-6
    np.testing.assert_allclose(hf / hs, hf_ref / hs, atol=5e-3)


def test_ssd_chunked_jnp_matches_ref():
    """The model's pure-jnp chunked path tracks the exact recurrence to
    bf16 accuracy (it intentionally runs bf16 matmuls)."""
    B, S, H, P, N = 2, 128, 4, 16, 8
    x = _mk((B, S, H, P), jnp.bfloat16, 30)
    dt = jax.nn.softplus(_mk((B, S, H), jnp.float32, 31))
    A = -jnp.exp(_mk((H,), jnp.float32, 32, 0.5))
    Bm = _mk((B, S, H, N), jnp.bfloat16, 33)
    Cm = _mk((B, S, H, N), jnp.bfloat16, 34)
    y, hf = ssd_chunked(x, dt, A, Bm, Cm, 32)
    y_ref, hf_ref = ref.ssd_ref(x, dt, A, Bm, Cm)
    scale = float(jnp.abs(y_ref.astype(jnp.float32)).max()) + 1e-6
    np.testing.assert_allclose(y.astype(np.float32) / scale,
                               y_ref.astype(np.float32) / scale, atol=2e-2)


def test_ssd_state_continuation():
    """Chunked scan with h0 from a previous segment == one long sequence."""
    B, S, H, P, N = 1, 128, 2, 16, 8
    x = _mk((B, S, H, P), jnp.float32, 40)
    dt = jax.nn.softplus(_mk((B, S, H), jnp.float32, 41))
    A = -jnp.exp(_mk((H,), jnp.float32, 42, 0.5))
    Bm = _mk((B, S, H, N), jnp.float32, 43)
    Cm = _mk((B, S, H, N), jnp.float32, 44)
    y_full, h_full = ssd_chunked(x, dt, A, Bm, Cm, 32)
    half = S // 2
    y1, h1 = ssd_chunked(x[:, :half], dt[:, :half], A, Bm[:, :half],
                         Cm[:, :half], 32)
    y2, h2 = ssd_chunked(x[:, half:], dt[:, half:], A, Bm[:, half:],
                         Cm[:, half:], 32, h0=h1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h2, h_full, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused dense-MoE
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("act", ["swiglu", "gelu"])
@pytest.mark.parametrize("T,d,E,f,tile", [
    (64, 32, 4, 16, 32),
    (128, 64, 8, 32, 64),
])
def test_moe_dense_kernel(T, d, E, f, tile, act):
    from repro.kernels.moe_dense import moe_dense
    from repro.kernels.ref import moe_dense_ref

    x = _mk((T, d), jnp.bfloat16, 50)
    wi = _mk((E, d, f), jnp.bfloat16, 51, 0.3)
    wg = _mk((E, d, f), jnp.bfloat16, 52, 0.3)
    wo = _mk((E, f, d), jnp.bfloat16, 53, 0.3)
    # top-2-of-E style sparse router weights
    raw = jax.nn.softmax(_mk((T, E), jnp.float32, 54), -1)
    top, idx = jax.lax.top_k(raw, 2)
    w = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], idx].set(
        top / top.sum(-1, keepdims=True))
    y = moe_dense(x, w, wi, wg, wo, act=act, tile_t=tile, interpret=True)
    y_ref = moe_dense_ref(x, w, wi, wg, wo, act=act)
    scale = float(jnp.abs(y_ref.astype(jnp.float32)).max()) + 1e-6
    np.testing.assert_allclose(y.astype(np.float32) / scale,
                               y_ref.astype(np.float32) / scale, atol=2e-2)


def test_moe_dense_kernel_matches_model_moe():
    """Kernel output == models/moe.py dense path on a full block."""
    import dataclasses
    from repro.configs import get_arch
    from repro.kernels.moe_dense import moe_dense
    from repro.models.moe import moe_apply, moe_param_specs
    from repro.sharding import init_spec_tree

    cfg = get_arch("granite-moe-3b-a800m").reduced()
    p = init_spec_tree(moe_param_specs(cfg), jax.random.PRNGKey(1))
    x = _mk((2, 32, cfg.d_model), jnp.bfloat16, 60)
    y_model, _ = moe_apply(cfg, p, x)
    # rebuild the router weights exactly as moe_apply does
    m = cfg.moe
    logits = jnp.einsum("bsd,de->bse",
                        x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    top = top / top.sum(-1, keepdims=True)
    oh = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32)
    w_te = jnp.einsum("bsk,bske->bse", top, oh)
    T = x.shape[0] * x.shape[1]
    y_k = moe_dense(x.reshape(T, -1), w_te.reshape(T, -1),
                    p["wi"], p["wg"], p["wo"], act=cfg.act,
                    tile_t=32).reshape(x.shape)
    scale = float(jnp.abs(y_model.astype(jnp.float32)).max()) + 1e-6
    np.testing.assert_allclose(y_k.astype(np.float32) / scale,
                               y_model.astype(np.float32) / scale,
                               atol=3e-2)
