"""Long-utterance Pallas BLSTM paths (sequence-chunked recompute + fused
multi-layer stack): gradient parity vs the unchunked kernels and the
masked-scan oracle, residual-stash accounting, and the joint
(block_b, seq_chunk) VMEM tuner.  All pallas calls run in interpret mode
(CPU CI); tolerances follow tests/test_kernels.py (f32 1e-4 / bf16 2e-2
normalized vs the oracle; the chunked-vs-unchunked comparison is much
tighter because the recompute replays the identical op sequence)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.lstm_cell import (DEFAULT_VMEM_BUDGET, _chunked_usage,
                                     _stack_usage, auto_stack_block_b,
                                     auto_tile, blstm_sequence,
                                     blstm_stack_sequence, lstm_sequence,
                                     stash_bytes)

KEY = jax.random.PRNGKey(7)


def _mk(shape, dtype, i=0, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, i), shape,
                              jnp.float32) * scale).astype(dtype)


def _mk_lstm(D, H, dtype, base):
    return (_mk((D, 4 * H), dtype, base, 0.3),
            _mk((H, 4 * H), dtype, base + 1, 0.3),
            _mk((4 * H,), jnp.float32, base + 2, 0.1))


def _norm_close(got, want, tol, name=""):
    scale = float(jnp.abs(want.astype(jnp.float32)).max()) + 1e-8
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=tol, err_msg=name)


def _sq_loss(fn):
    def loss(*args):
        return jnp.mean(jnp.square(fn(*args).astype(jnp.float32)))
    return loss


# ---------------------------------------------------------------------------
# sequence-chunked recompute
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("T,K", [
    (12, 4),     # K divides T
    (13, 5),     # non-dividing T -> time padding + synthesized lengths
])
def test_seq_chunk_grad_parity(T, K, reverse, dtype):
    """Chunked-recompute grads match (a) the scan oracle at the standard
    tolerances and (b) the unchunked per-step-stash kernel near-exactly
    (the recompute replays the identical op sequence from the stashed
    f32 chunk-entry carries)."""
    B, D, H = 4, 8, 16
    wx, wh, b = _mk_lstm(D, H, dtype, 10)
    x = _mk((B, T, D), dtype, 13)

    loss_c = _sq_loss(lambda *a: lstm_sequence(
        *a, reverse=reverse, interpret=True, seq_chunk=K))
    loss_u = _sq_loss(lambda *a: lstm_sequence(
        *a, reverse=reverse, interpret=True))
    loss_r = _sq_loss(lambda *a: ref.lstm_ref(*a, reverse=reverse))

    argn = (0, 1, 2, 3)
    v_c, g_c = jax.value_and_grad(loss_c, argnums=argn)(wx, wh, b, x)
    v_u, g_u = jax.value_and_grad(loss_u, argnums=argn)(wx, wh, b, x)
    v_r, g_r = jax.value_and_grad(loss_r, argnums=argn)(wx, wh, b, x)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(float(v_c), float(v_r), rtol=tol)
    for got, exact, want, name in zip(g_c, g_u, g_r,
                                      ("dwx", "dwh", "db", "dx")):
        assert got.dtype == want.dtype
        _norm_close(got, want, tol, name)
        _norm_close(got, exact, 2e-5, name + " vs unchunked")


def test_seq_chunk_varlen_blstm_grad():
    """Chunked recompute composes with the PR-2 masking semantics: a
    fused BLSTM over a variable-length batch (incl. length-1 rows and a
    non-dividing T) with batch tiling matches the masked-scan oracle."""
    B, T, D, H, K = 5, 11, 8, 16, 4
    wf = _mk_lstm(D, H, jnp.bfloat16, 20)
    wb = _mk_lstm(D, H, jnp.bfloat16, 24)
    x = _mk((B, T, D), jnp.bfloat16, 28)
    lens = jnp.array([11, 3, 7, 1, 5], jnp.int32)

    loss_k = _sq_loss(lambda *a: blstm_sequence(
        *a, lens, interpret=True, seq_chunk=K, block_b=2))
    loss_r = _sq_loss(lambda *a: ref.blstm_ref(*a, lengths=lens))
    args = (*wf, *wb, x)
    argn = tuple(range(7))
    v_k, g_k = jax.value_and_grad(loss_k, argnums=argn)(*args)
    v_r, g_r = jax.value_and_grad(loss_r, argnums=argn)(*args)
    np.testing.assert_allclose(float(v_k), float(v_r), rtol=2e-2)
    names = ("dwxf", "dwhf", "dbf", "dwxb", "dwhb", "dbb", "dx")
    for got, want, name in zip(g_k, g_r, names):
        _norm_close(got, want, 2e-2, name)


def test_seq_chunk_auto_end_to_end():
    """seq_chunk=-1 (joint auto-tuning) trains end-to-end and matches the
    oracle."""
    B, T, D, H = 4, 10, 8, 16
    wx, wh, b = _mk_lstm(D, H, jnp.float32, 30)
    x = _mk((B, T, D), jnp.float32, 33)
    loss_c = _sq_loss(lambda *a: lstm_sequence(
        *a, interpret=True, seq_chunk=-1))
    loss_r = _sq_loss(ref.lstm_ref)
    v_c, g_c = jax.value_and_grad(loss_c, argnums=(0, 1, 2, 3))(wx, wh, b, x)
    v_r, g_r = jax.value_and_grad(loss_r, argnums=(0, 1, 2, 3))(wx, wh, b, x)
    np.testing.assert_allclose(float(v_c), float(v_r), rtol=1e-4)
    for got, want in zip(g_c, g_r):
        _norm_close(got, want, 1e-4)


def test_auto_tile_fits_budget():
    """The joint (block_b, seq_chunk) tuner respects the VMEM budget, the
    explicit-K / explicit-bb contracts, and clamps K to T."""
    # paper shape, bf16 weights: the returned pair must fit the budget
    bb, K = auto_tile(256, 8000, 260, 512, 2, n_dir=2, seq_chunk=-1)
    assert _chunked_usage(bb, K, 260, 512, 2, 2, 4) <= DEFAULT_VMEM_BUDGET
    assert bb >= 8 and K >= 16
    # explicit K is respected (clamped to T), bb still tuned
    bb2, K2 = auto_tile(256, 8000, 260, 512, 2, n_dir=2, seq_chunk=64)
    assert K2 == 64 and bb2 >= 8
    _, K3 = auto_tile(256, 8, 260, 512, 2, n_dir=2, seq_chunk=64)
    assert K3 == 8            # clamped to T
    # explicit block_b is passed through untouched
    bb4, _ = auto_tile(256, 8000, 260, 512, 2, n_dir=2, seq_chunk=-1,
                       block_b=16)
    assert bb4 == 16
    # seq_chunk=0 degrades to the unchunked auto_block_b contract
    bb5, K5 = auto_tile(256, 21, 260, 512, 2, n_dir=2, seq_chunk=0)
    assert K5 == 0 and bb5 >= 8
    # auto K bounds the masked time padding: an unlucky T just past a
    # power of two must not pad by ~2x (260 -> 512); waste stays <= T/8
    # (or K has hit its 16-frame floor)
    _, K6 = auto_tile(16, 260, 64, 64, 4, seq_chunk=-1)
    Tp = -(-260 // K6) * K6
    assert (Tp - 260) * 8 <= 260 or K6 == 16


def test_stash_bytes_accounting():
    """Acceptance: at T=8000 the chunked residual stash is <= 1/4 of the
    unchunked one (it is ~2/(5K) of it), and the formulas match the
    stash layouts (5H per step unchunked; 2H per chunk boundary)."""
    B, H = 256, 512
    full = stash_bytes(B, 8000, H, n_dir=2)
    assert full == 2 * B * 8000 * 5 * H * 4
    _, K = auto_tile(B, 8000, 260, H, 2, n_dir=2, seq_chunk=-1)
    chunked = stash_bytes(B, 8000, H, n_dir=2, seq_chunk=K)
    assert chunked == 2 * B * (-(-8000 // K)) * 2 * H * 4
    assert chunked <= full / 4
    # bf16 stash option halves both
    assert stash_bytes(B, 8000, H, n_dir=2, stash_itemsize=2) == full // 2
    # non-dividing T rounds the chunk count up
    assert stash_bytes(1, 13, H, seq_chunk=5) == 3 * 2 * H * 4


# ---------------------------------------------------------------------------
# fused multi-layer stack
# ---------------------------------------------------------------------------

def _mk_stack(L, D0, H, base=40):
    layers = []
    for i in range(L):
        Din = D0 if i == 0 else 2 * H
        layers.append(_mk_lstm(Din, H, jnp.bfloat16, base + 6 * i)
                      + _mk_lstm(Din, H, jnp.bfloat16, base + 6 * i + 3))
    return tuple(layers)


@pytest.mark.parametrize("masked", [False, True])
def test_blstm_stack_bitidentical(masked, capsys):
    """Acceptance: the fused multi-layer kernel is bit-identical to the
    per-layer blstm_sequence loop (dense and masked, tiled batch with a
    non-dividing block_b), and tracks the stacked-scan oracle."""
    B, T, D0, H, L = 5, 9, 12, 16, 3
    layers = _mk_stack(L, D0, H)
    x = _mk((B, T, D0), jnp.bfloat16, 60)
    lens = jnp.array([9, 2, 7, 1, 5], jnp.int32) if masked else None

    fused = blstm_stack_sequence(layers, x, lens, interpret=True, block_b=2)
    assert "blstm stack: fused" in capsys.readouterr().out
    loop = x
    for lw in layers:
        loop = blstm_sequence(*lw, loop, lens, interpret=True, block_b=2)
    np.testing.assert_array_equal(np.asarray(fused, np.float32),
                                  np.asarray(loop, np.float32))
    _norm_close(fused, ref.blstm_stack_ref(layers, x, lens), 3e-2)


def test_blstm_stack_grad_matches_per_layer():
    """Under jax.vjp the fused stack falls back to the per-layer stashing
    custom VJP — its grads match differentiating the per-layer pallas
    loop, composing with lengths and seq_chunk."""
    B, T, D0, H, L = 4, 10, 12, 16, 2
    layers = _mk_stack(L, D0, H, base=70)
    x = _mk((B, T, D0), jnp.bfloat16, 90)
    lens = jnp.array([10, 3, 8, 5], jnp.int32)

    def loss_stack(ls, x):
        y = blstm_stack_sequence(ls, x, lens, interpret=True, seq_chunk=4)
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    def loss_loop(ls, x):
        h = x
        for lw in ls:
            h = blstm_sequence(*lw, h, lens, interpret=True)
        return jnp.mean(jnp.square(h.astype(jnp.float32)))

    v_s, g_s = jax.value_and_grad(loss_stack, argnums=(0, 1))(layers, x)
    v_l, g_l = jax.value_and_grad(loss_loop, argnums=(0, 1))(layers, x)
    np.testing.assert_allclose(float(v_s), float(v_l), rtol=1e-2)
    flat_s = jax.tree.leaves(g_s)
    flat_l = jax.tree.leaves(g_l)
    assert len(flat_s) == len(flat_l) == 6 * L + 1
    for got, want in zip(flat_s, flat_l):
        assert got.dtype == want.dtype
        _norm_close(got, want, 2e-2)


def test_auto_stack_block_b_shrinks_with_T():
    """The fused-stack tile accounts for the (bB, T, 2H) ping-pong
    buffers: longer sequences get smaller tiles, floored at 8 rows."""
    bb_short = auto_stack_block_b(256, 21, 260, 512, 2)
    bb_long = auto_stack_block_b(256, 2000, 260, 512, 2)
    assert bb_short >= bb_long >= 8
    assert auto_stack_block_b(4, 8, 12, 16, 2) == 8   # tiny: one tile


def test_stack_fallback_when_buffers_overrun_budget(capsys):
    """When even the floor tile cannot hold the ping-pong buffers (very
    long T for the budget), the stack primal degrades to the per-layer
    loop — same numbers, T-independent VMEM — and prints that it did."""
    B, T, D0, H, L = 4, 16, 12, 16, 2
    layers = _mk_stack(L, D0, H, base=100)
    x = _mk((B, T, D0), jnp.bfloat16, 112)
    # a budget so small the 8-row floor overruns it -> fallback path
    tiny = 4096
    assert _stack_usage(8, T, D0, H, 2) > tiny
    fused = blstm_stack_sequence(layers, x, interpret=True,
                                 vmem_budget=tiny)
    assert "blstm stack: per-layer" in capsys.readouterr().out
    loop = x
    for lw in layers:
        loop = blstm_sequence(*lw, loop, interpret=True, vmem_budget=tiny)
    np.testing.assert_array_equal(np.asarray(fused, np.float32),
                                  np.asarray(loop, np.float32))


# ---------------------------------------------------------------------------
# model integration
# ---------------------------------------------------------------------------

def test_forward_pallas_stack_and_seq_chunk_loss_train():
    """models/lstm.forward's pallas path (now the fused stack) matches the
    jax scan path, and loss_train grads with lstm_seq_chunk set match the
    jax autodiff grads on a var-len batch."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.sharding import init_spec_tree

    cfg = dataclasses.replace(get_arch("swb2000-blstm").reduced(),
                              n_layers=2, lstm_hidden=16, lstm_bottleneck=8,
                              input_dim=12, vocab=32, lstm_block_b=2,
                              lstm_seq_chunk=4)
    model = build_model(cfg)
    params = init_spec_tree(model.param_specs(), jax.random.PRNGKey(0))
    B, T = 4, 6
    batch = {
        "features": np.asarray(_mk((B, T, cfg.input_dim), jnp.float32, 95)),
        "labels": np.asarray(
            jax.random.randint(KEY, (B, T), 0, cfg.vocab, jnp.int32)),
        "lengths": np.array([6, 2, 5, 3], np.int32),
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
