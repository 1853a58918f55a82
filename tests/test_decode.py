"""Recognition-quality subsystem tests: CTC prefix beam search (jnp +
Pallas) vs the numpy oracle and greedy best-path, streaming/chunked
decode, the eval metrics satellites, and the evaluate/serve loops."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import decode as DC
from repro.decode.beam import NEG, BeamState
from repro.decode.kernel import argmax_tokens, auto_block_b_decode
from repro.decode.ref import prefix_beam_ref
from repro.eval.metrics import (collapse_labels, edit_distance,
                                frame_error_rate, greedy_ctc_decode,
                                token_error_rate)


def _rand_logits(seed, B, T, V, scale=2.0):
    rng = np.random.default_rng(seed)
    return (scale * rng.normal(size=(B, T, V))).astype(np.float32)


def _rand_lengths(seed, B, T):
    rng = np.random.default_rng(seed + 1)
    return rng.integers(1, T + 1, size=B).astype(np.int32)


# ---------------------------------------------------------------------------
# beam=1 == greedy best-path (the acceptance bit-match)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_beam1_max_bitmatches_greedy(seed):
    logits = _rand_logits(seed, B=5, T=16, V=9)
    hyp = DC.beam_decode(jnp.asarray(logits), beam=1, semiring="max")
    assert hyp == greedy_ctc_decode(logits)


@pytest.mark.parametrize("seed", [0, 1])
def test_beam1_max_bitmatches_greedy_varlen(seed):
    logits = _rand_logits(seed, B=5, T=16, V=9)
    lens = _rand_lengths(seed, 5, 16)
    hyp = DC.beam_decode(jnp.asarray(logits), jnp.asarray(lens), beam=1,
                         semiring="max")
    assert hyp == greedy_ctc_decode(logits, lens)


# ---------------------------------------------------------------------------
# vectorized beam vs the dict-of-prefixes numpy oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("semiring", ["max", "sum"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_beam_matches_oracle(semiring, seed):
    logits = _rand_logits(seed, B=4, T=12, V=7)
    hyp = DC.beam_decode(jnp.asarray(logits), beam=4, semiring=semiring)
    ref, _ = prefix_beam_ref(logits, beam=4, semiring=semiring)
    assert hyp == ref


@pytest.mark.parametrize("semiring", ["max", "sum"])
def test_beam_matches_oracle_varlen(semiring):
    logits = _rand_logits(7, B=5, T=14, V=6)
    lens = np.array([14, 7, 1, 10, 3], np.int32)
    hyp = DC.beam_decode(jnp.asarray(logits), jnp.asarray(lens), beam=4,
                         semiring=semiring)
    ref, _ = prefix_beam_ref(logits, lens, beam=4, semiring=semiring)
    assert hyp == ref


def test_beam_scores_match_oracle():
    logits = _rand_logits(11, B=3, T=10, V=6)
    _, _, scores = DC.beam_search(jnp.asarray(logits), beam=4,
                                  semiring="sum")
    _, ref_scores = prefix_beam_ref(logits, beam=4, semiring="sum")
    np.testing.assert_allclose(np.asarray(scores), ref_scores,
                               rtol=1e-5, atol=1e-5)


def test_len_norm_reranks_final_beams():
    # beam A: 1 token, raw score -1; beam B: 4 tokens, raw score -2.
    # Raw ranking picks A; alpha=1 normalizes to -1 vs -0.5 and picks B.
    tokens = jnp.full((1, 2, 6), -1, jnp.int32)
    tokens = tokens.at[0, 0, 0].set(3)
    tokens = tokens.at[0, 1, :4].set(jnp.array([1, 2, 1, 2]))
    state = BeamState(
        tokens=tokens,
        lens=jnp.array([[1, 4]], jnp.int32),
        last=jnp.array([[3, 2]], jnp.int32),
        phash=jnp.zeros((1, 2), jnp.int32),
        p_b=jnp.array([[-1.0, -2.0]], jnp.float32),
        p_nb=jnp.full((1, 2), NEG, jnp.float32),
        t=jnp.zeros((1,), jnp.int32),
    )
    toks0, lens0, _ = DC.finalize(state, len_norm=0.0)
    toks1, lens1, _ = DC.finalize(state, len_norm=1.0)
    assert int(lens0[0]) == 1 and list(toks0[0][:1]) == [3]
    assert int(lens1[0]) == 4 and list(toks1[0][:4]) == [1, 2, 1, 2]


# ---------------------------------------------------------------------------
# sum semiring > best path (the reason beam search exists)
# ---------------------------------------------------------------------------

def test_sum_beam_recovers_mass_best_path_drops():
    # Per frame: p(blank)=.4, p(a)=.3, p(b)=.3.  Best path is blank,blank
    # (.16) -> [], but prefix [a] sums (a,a)+(a,-)+(-,a) = .33 -> [a].
    p = np.log(np.array([0.4, 0.3, 0.3], np.float32))
    logits = np.broadcast_to(p, (1, 2, 3)).copy()
    assert greedy_ctc_decode(logits) == [[]]
    assert DC.beam_decode(jnp.asarray(logits), beam=3,
                          semiring="sum") in ([[1]], [[2]])
    ref, _ = prefix_beam_ref(logits, beam=3, semiring="sum")
    assert DC.beam_decode(jnp.asarray(logits), beam=3,
                          semiring="sum") == ref


# ---------------------------------------------------------------------------
# edge cases: all-blank and repeat collapse
# ---------------------------------------------------------------------------

def test_all_blank_decodes_empty():
    logits = np.zeros((2, 8, 5), np.float32)
    logits[:, :, 0] = 6.0
    for impl in ("jax", "pallas"):
        assert DC.beam_decode(jnp.asarray(logits), beam=4, impl=impl,
                              interpret=True) == [[], []]


def test_repeat_collapse_and_blank_separated_repeat():
    # path 1,1,blank,1,2,2 -> [1,1,2]: repeats merge, blank splits them
    V = 4
    path = [1, 1, 0, 1, 2, 2]
    logits = np.full((1, len(path), V), -4.0, np.float32)
    for t, c in enumerate(path):
        logits[0, t, c] = 4.0
    for semiring in ("max", "sum"):
        for impl in ("jax", "pallas"):
            hyp = DC.beam_decode(jnp.asarray(logits), beam=4,
                                 semiring=semiring, impl=impl,
                                 interpret=True)
            assert hyp == [[1, 1, 2]], (semiring, impl, hyp)


# ---------------------------------------------------------------------------
# pallas kernel vs jnp path (bit parity) under variable lengths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("semiring", ["max", "sum"])
def test_pallas_beam_bitmatches_jax_varlen(semiring):
    logits = _rand_logits(3, B=5, T=10, V=8)
    lens = np.array([10, 4, 1, 7, 9], np.int32)
    tj, lj, sj = DC.beam_search(jnp.asarray(logits), jnp.asarray(lens),
                                beam=4, semiring=semiring, impl="jax")
    tp, lp, sp = DC.beam_search(jnp.asarray(logits), jnp.asarray(lens),
                                beam=4, semiring=semiring, impl="pallas",
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(tj), np.asarray(tp))
    np.testing.assert_array_equal(np.asarray(lj), np.asarray(lp))
    np.testing.assert_array_equal(np.asarray(sj), np.asarray(sp))


def test_pallas_beam_batch_tiling_and_padding():
    """block_b that doesn't divide B exercises the pad/slice path."""
    logits = _rand_logits(5, B=5, T=8, V=6)
    tj, lj, _ = DC.beam_search(jnp.asarray(logits), beam=3, impl="jax")
    tp, lp, _ = DC.beam_search(jnp.asarray(logits), beam=3, impl="pallas",
                               interpret=True, block_b=2)
    np.testing.assert_array_equal(np.asarray(tj), np.asarray(tp))
    np.testing.assert_array_equal(np.asarray(lj), np.asarray(lp))


def test_auto_block_b_decode_fits_budget():
    bb = auto_block_b_decode(256, beam=8, vocab=32_000,
                             vmem_budget=12 * 2 ** 20)
    assert 1 <= bb <= 256
    assert (4 * 8 * 32_000 + 32_000) * 4 * bb <= 12 * 2 ** 20
    assert auto_block_b_decode(4, beam=4, vocab=16) == 4   # capped at B


# ---------------------------------------------------------------------------
# top-C vocab pruning: exactness under covering C (docs/decoding.md)
# ---------------------------------------------------------------------------

def _peaky_logits(seed, B, T, V, support):
    """Planted-path posteriors whose per-frame support (tokens with any
    realistic mass) is {0..support-1}: the +12 margin puts every other
    token ~e^-12 below, so any C >= support covers the extend support
    and the pruned search must be bit-identical to the unpruned one."""
    rng = np.random.default_rng(seed)
    path = rng.integers(0, support, size=(B, T)).astype(np.int32)
    path[rng.random((B, T)) < 0.4] = 0
    logits = rng.normal(0.0, 1.0, size=(B, T, V)).astype(np.float32)
    logits[..., support:] -= 12.0
    logits += 4.0 * (np.arange(V)[None, None, :] == path[:, :, None])
    return logits


def test_topc_scores_matches_lax_topk():
    logp = jax.nn.log_softmax(
        jnp.asarray(_rand_logits(3, B=5, T=1, V=33)[:, 0]), -1)
    vals, idx = DC.topc_scores(logp, 7)
    ref_v, ref_i = jax.lax.top_k(logp, 7)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_i))


@pytest.mark.parametrize("semiring", ["max", "sum"])
@pytest.mark.parametrize("impl", ["jax", "pallas"])
@pytest.mark.parametrize("topc", [8, 31])
def test_topc_covering_bitmatches_unpruned(semiring, impl, topc):
    logits = _peaky_logits(5, B=4, T=18, V=32, support=6)
    lens = _rand_lengths(5, 4, 18)
    ref = DC.beam_search(jnp.asarray(logits), jnp.asarray(lens), beam=4,
                         semiring=semiring)
    out = DC.beam_search(jnp.asarray(logits), jnp.asarray(lens), beam=4,
                         semiring=semiring, impl=impl, topc=topc)
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


@pytest.mark.parametrize("semiring", ["max", "sum"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_topc_pruned_matches_oracle(semiring, seed):
    """Property test: the pruned beam with covering C reproduces the
    dict-of-real-prefixes numpy oracle exactly."""
    logits = _peaky_logits(seed, B=4, T=12, V=24, support=5)
    hyp = DC.beam_decode(jnp.asarray(logits), beam=4, semiring=semiring,
                         topc=8)
    ref, _ = prefix_beam_ref(logits, beam=4, semiring=semiring)
    assert hyp == ref


def test_topc_chunked_streaming_bitmatches_oneshot():
    logits = _peaky_logits(7, B=4, T=14, V=20, support=5)
    lens = np.array([14, 6, 2, 11], np.int32)
    ref = DC.beam_search(jnp.asarray(logits), jnp.asarray(lens), beam=4,
                         semiring="sum", topc=8)
    st = DC.init_state(4, 4, 14)
    for t0 in range(0, 14, 5):
        st = DC.decode_chunk(st, jnp.asarray(logits[:, t0:t0 + 5]),
                             jnp.asarray(lens), semiring="sum", topc=8)
    out = DC.finalize(st, semiring="sum")
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


def test_topc_at_least_vocab_routes_unpruned():
    """topc >= V is the unpruned path (same object-level step), so the
    bench's C=V row is the true baseline."""
    logits = _rand_logits(11, B=3, T=10, V=16)
    ref = DC.beam_search(jnp.asarray(logits), beam=4, semiring="sum")
    out = DC.beam_search(jnp.asarray(logits), beam=4, semiring="sum",
                         topc=16)
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


def test_beam_cand_bytes_scales_with_c_not_v():
    from repro.decode.kernel import beam_cand_bytes

    unpruned = beam_cand_bytes(8, 32_000)
    pruned = beam_cand_bytes(8, 32_000, topc=64)
    assert unpruned == (4 * 8 * 32_000 + 32_000) * 4   # legacy formula
    assert pruned < unpruned / 4
    # doubling vocab barely moves the pruned set (logp block only) ...
    assert beam_cand_bytes(8, 64_000, topc=64) < 2.2 * pruned
    # ... while block_b grows accordingly
    assert (auto_block_b_decode(1 << 20, 8, 32_000, topc=64)
            > 4 * auto_block_b_decode(1 << 20, 8, 32_000))


# ---------------------------------------------------------------------------
# streaming: chunked == one-shot, reset_rows re-arms slots
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [(5, 5, 4), (1,) * 14, (3, 11)])
def test_chunked_decode_bitmatches_oneshot(chunks):
    assert sum(chunks) == 14
    logits = _rand_logits(9, B=4, T=14, V=6)
    lens = np.array([14, 6, 2, 11], np.int32)
    ref_t, ref_l, ref_s = DC.beam_search(
        jnp.asarray(logits), jnp.asarray(lens), beam=4, semiring="sum")
    st = DC.init_state(4, 4, 14)
    t0 = 0
    for c in chunks:
        st = DC.decode_chunk(st, jnp.asarray(logits[:, t0:t0 + c]),
                             jnp.asarray(lens), semiring="sum")
        t0 += c
    toks, ls, sc = DC.finalize(st, semiring="sum")
    np.testing.assert_array_equal(np.asarray(ref_t), np.asarray(toks))
    np.testing.assert_array_equal(np.asarray(ref_l), np.asarray(ls))
    np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(sc))


def test_reset_rows_rearms_only_masked_rows():
    logits = _rand_logits(2, B=3, T=6, V=5)
    st = DC.init_state(3, 3, 6)
    st = DC.decode_chunk(st, jnp.asarray(logits))
    mask = jnp.array([False, True, False])
    st2 = DC.reset_rows(st, mask)
    fresh = DC.init_state(3, 3, 6)
    np.testing.assert_array_equal(np.asarray(st2.tokens[1]),
                                  np.asarray(fresh.tokens[1]))
    assert int(st2.t[1]) == 0
    np.testing.assert_array_equal(np.asarray(st2.tokens[0]),
                                  np.asarray(st.tokens[0]))
    np.testing.assert_array_equal(np.asarray(st2.p_b[2]),
                                  np.asarray(st.p_b[2]))


def test_beam_occupancy():
    st = DC.init_state(2, 4, 6)
    occ = np.asarray(DC.beam_occupancy(st))
    np.testing.assert_allclose(occ, [0.25, 0.25])   # only the empty root
    logits = _rand_logits(4, B=2, T=6, V=8)
    st = DC.decode_chunk(st, jnp.asarray(logits))
    occ = np.asarray(DC.beam_occupancy(st))
    np.testing.assert_allclose(occ, [1.0, 1.0])     # beams fill (V >= K)


# ---------------------------------------------------------------------------
# serving argmax kernel
# ---------------------------------------------------------------------------

def test_argmax_tokens_matches_jnp():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(5, 33)).astype(np.float32)
    out = argmax_tokens(jnp.asarray(logits), interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  logits.argmax(-1).astype(np.int32))
    out2 = argmax_tokens(jnp.asarray(logits), interpret=True, block_b=2)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))


# ---------------------------------------------------------------------------
# eval metrics satellites
# ---------------------------------------------------------------------------

def _edit_distance_percell(ref, hyp):
    """The pre-vectorization per-cell DP (frozen here as the parity
    reference for the numpy row-sweep implementation)."""
    ref, hyp = list(ref), list(hyp)
    m, n = len(ref), len(hyp)
    dp = np.arange(n + 1)
    for i in range(1, m + 1):
        prev_diag = dp[0]
        dp[0] = i
        for j in range(1, n + 1):
            cur = dp[j]
            dp[j] = min(dp[j] + 1,
                        dp[j - 1] + 1,
                        prev_diag + (ref[i - 1] != hyp[j - 1]))
            prev_diag = cur
    return int(dp[n])


def test_edit_distance_vectorized_parity():
    rng = np.random.default_rng(0)
    for _ in range(200):
        a = list(rng.integers(0, 5, size=rng.integers(0, 12)))
        b = list(rng.integers(0, 5, size=rng.integers(0, 12)))
        assert edit_distance(a, b) == _edit_distance_percell(a, b), (a, b)


def test_frame_error_rate_masks_padding():
    logits = np.zeros((2, 4, 3), np.float32)
    logits[:, :, 1] = 5.0                       # predicts class 1 always
    labels = np.array([[1, 1, 2, 2], [1, 2, 0, 0]], np.int32)
    # unmasked: errors at (0,2),(0,3),(1,1),(1,2),(1,3) -> 5/8
    assert frame_error_rate(logits, labels) == pytest.approx(5 / 8)
    # lengths (2, 2): only frames t<2 count -> errors at (1,1) -> 1/4
    assert frame_error_rate(logits, labels,
                            np.array([2, 2])) == pytest.approx(1 / 4)


def test_greedy_ctc_decode_respects_lengths():
    logits = np.zeros((1, 4, 3), np.float32)
    for t, c in enumerate([1, 1, 2, 2]):
        logits[0, t, c] = 5.0
    assert greedy_ctc_decode(logits) == [[1, 2]]
    assert greedy_ctc_decode(logits, np.array([2])) == [[1]]


def test_collapse_labels():
    labels = np.array([[0, 1, 1, 2, 0, 2], [3, 3, 3, 0, 0, 0]], np.int32)
    assert collapse_labels(labels) == [[1, 2, 2], [3]]
    assert collapse_labels(labels, np.array([3, 2])) == [[1], [3]]
    assert collapse_labels(np.zeros((1, 4), np.int32)) == [[]]


# ---------------------------------------------------------------------------
# evaluate + ASR serving end-to-end (tiny shapes)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.configs import get_arch

    return dataclasses.replace(
        get_arch("swb2000-blstm").reduced(), n_layers=1, lstm_hidden=32,
        lstm_bottleneck=16, input_dim=16, vocab=32, beam_width=3)


def test_evaluate_restores_checkpoint_end_to_end(tmp_path):
    """train (2 steps) -> checkpoint -> restore_consensus ->
    evaluate_params reports finite TER/FER rows."""
    from repro.checkpoint import save
    from repro.launch.evaluate import evaluate_params, restore_consensus
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import setup_training

    cfg = _tiny_cfg()
    mesh = make_local_mesh()
    state, step_fn, meta = setup_training(cfg, mesh, strategy_name="ad_psgd",
                                          n_learners=2)
    from repro.data import make_dataset

    ds = make_dataset(cfg, seq_len=12, batch=4, seed=0)
    with jax.set_mesh(mesh):
        for k in range(2):
            state, _ = step_fn(state, ds.batch_at(k))
    save(str(tmp_path / "ck"), 2, state)

    params, step, meta2 = restore_consensus(
        cfg, ckpt_dir=str(tmp_path / "ck"), strategy_name="ad_psgd",
        n_learners=2)
    assert step == 2
    m = evaluate_params(cfg, params, batches=1, batch=4, seq_len=12,
                        var_len=True, decode_chunk=5)
    assert 0.0 <= m["fer"] <= 1.0
    assert np.isfinite(m["ter_greedy"]) and np.isfinite(m["ter_beam"])
    assert m["frames_per_s"] > 0 and m["decoded_tok_per_s"] >= 0
    assert 0.0 < m["beam_occupancy"] <= 1.0


def test_asr_server_streaming_matches_oneshot_decode():
    """The serving loop's chunked slot decode must equal a one-shot
    beam_search over the same posteriors (carry = beam state)."""
    from repro.launch.serve import AsrServer
    from repro.models import lstm as LS

    cfg = _tiny_cfg()
    server = AsrServer(cfg, slots=2, max_frames=16, chunk=5, beam=3)
    rng = np.random.default_rng(0)
    reqs = [(i, rng.normal(size=(n, cfg.input_dim)).astype(np.float32))
            for i, n in [(0, 13), (1, 7), (2, 16)]]
    pending = list(reqs)
    finished = []
    waves = 0
    while pending or server.active.any():
        while pending and server.admit(*pending[0]):
            pending.pop(0)
        done, occ = server.step()
        finished += done
        waves += 1
        assert 0.0 <= occ <= 1.0
        assert waves < 50
    assert sorted(r for r, _ in finished) == [0, 1, 2]

    hyps = dict(finished)
    for rid, feats in reqs:
        n = len(feats)
        padded = np.zeros((1, 16, cfg.input_dim), np.float32)
        padded[0, :n] = feats
        logits = LS.forward(cfg, server.params, jnp.asarray(padded),
                            jnp.asarray([n], jnp.int32))
        toks, lens, _ = DC.beam_search(
            logits, jnp.asarray([n], jnp.int32), beam=3,
            semiring=server.semiring)
        want = list(map(int, np.asarray(toks)[0][:int(lens[0])]))
        assert hyps[rid] == want, (rid, hyps[rid], want)


def test_ter_drops_after_ctc_training_beam_not_worse_than_greedy():
    """Short CTC training: consensus TER must drop and the sum-semiring
    beam must not be worse than greedy on the heldout set."""
    from repro.models import lstm as LS
    from repro.models.ctc import collapse_frame_labels, ctc_loss
    from repro.sharding import init_spec_tree
    from repro.data import make_dataset
    from repro.models import build_model

    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = init_spec_tree(model.param_specs(), jax.random.PRNGKey(0))
    ds = make_dataset(cfg, seq_len=12, batch=8, seed=0)

    def loss_fn(p, f, s):
        return ctc_loss(LS.forward(cfg, p, f), s)

    @jax.jit
    def step(p, f, s):
        l, g = jax.value_and_grad(loss_fn)(p, f, s)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                          for x in jax.tree.leaves(g)))
        sc = jnp.minimum(1.0, 5.0 / (gn + 1e-6)) * 0.05
        return l, jax.tree.map(
            lambda w, gg: (w.astype(jnp.float32)
                           - sc * gg.astype(jnp.float32)).astype(w.dtype),
            p, g)

    def ters(p):
        b = ds.batch_at(9_999)
        seqs, lens = collapse_frame_labels(b["labels"], max_len=5)
        refs = [list(s[:n]) for s, n in zip(seqs, lens)]
        logits = np.asarray(LS.forward(cfg, p, jnp.asarray(b["features"])),
                            np.float32)
        tg = token_error_rate(refs, greedy_ctc_decode(logits))
        tb = token_error_rate(refs, DC.beam_decode(
            jnp.asarray(logits), beam=4, semiring="sum"))
        return tg, tb

    t0g, _ = ters(params)
    for k in range(60):
        b = ds.batch_at(k)
        seqs, _ = collapse_frame_labels(b["labels"], max_len=5)
        _, params = step(params, jnp.asarray(b["features"]),
                         jnp.asarray(seqs))
    t1g, t1b = ters(params)
    assert t1g < t0g - 0.05, (t0g, t1g)
    assert t1b <= t1g + 1e-9, (t1b, t1g)
