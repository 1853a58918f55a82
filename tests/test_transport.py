"""The unified communication substrate (repro.core.transport).

* GOLDEN refactor-equivalence: with the default f32 wire every strategy's
  update trajectory is BIT-IDENTICAL to a frozen re-implementation of the
  pre-substrate ``make_train_step`` (the acceptance gate of the refactor).
* Wire codecs: int8 per-sender bound, topk difference coding, bf16.
* Topology math: hierarchical == kron matrix, pod degenerations.
* Composability: int8/topk converge under sc_psgd / ad_psgd / bmuf to the
  f32 trajectory within tolerance.
* Error-feedback state: f32 residuals under bf16 params, 100-round drift.
* Wire-byte accounting: int8 <= 0.27x f32 on the real BLSTM param tree.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mixing
from repro.core import strategies as ST
from repro.core.transport import Transport, decode_payload
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant

W_TRUE = jax.random.normal(jax.random.PRNGKey(7), (8,))


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def data(seed, n=64):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 8))
    return {"x": x, "y": x @ W_TRUE}


# ---------------------------------------------------------------------------
# GOLDEN: bit-identical to the pre-substrate step under the default wire
# ---------------------------------------------------------------------------

_LEGACY_MIXERS = {
    "sc_psgd_replicated": mixing.mix_uniform,
    "sd_psgd": mixing.mix_ring,
    "ad_psgd": mixing.mix_ring,
    "downpour": mixing.mix_uniform,
    "hring": mixing.mix_ring,          # pre-substrate hring == plain ring
    "bmuf": mixing.mix_uniform,        # block-sync averaging
}


def _legacy_step_factory(strategy, optimizer, lr_schedule, n_learners):
    """Frozen copy of the PRE-substrate make_train_step (replicated,
    rectangular-batch path) — the oracle for refactor equivalence."""
    legacy_mix = _LEGACY_MIXERS[strategy.name]

    def step(state, batch):
        lr = lr_schedule(state["step"])
        lbatch = ST.split_learner_batch(batch, n_learners)
        grad_at = state["prev_params"] if strategy.stale else state["params"]
        loss_l, g_l = jax.vmap(
            lambda p, b: jax.value_and_grad(loss_fn)(p, b))(grad_at, lbatch)
        metrics = {"loss": jnp.mean(loss_l)}

        if strategy.block_size:
            upd_params, opt = jax.vmap(
                optimizer.update, in_axes=(0, 0, 0, None)
            )(g_l, state["opt"], state["params"], lr)
            step_no = state["step"] + 1
            is_sync = (step_no % strategy.block_size) == 0

            def do_sync(args):
                params, anchor, mom = args
                avg = legacy_mix(params)
                delta = jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32)
                                  - b.astype(jnp.float32)), avg, anchor)
                mom = jax.tree.map(
                    lambda m, d: strategy.block_momentum * m
                    + strategy.block_lr * d, mom, delta)
                new = jax.tree.map(
                    lambda b, m: (b.astype(jnp.float32) + m).astype(b.dtype),
                    anchor, mom)
                return new, new, mom

            def no_sync(args):
                return args

            new_params, anchor, mom = jax.lax.cond(
                is_sync, do_sync, no_sync,
                (upd_params, state["anchor"], state["block_mom"]))
            out = {"params": new_params, "opt": opt, "step": step_no,
                   "anchor": anchor, "block_mom": mom}
        else:
            mixed = legacy_mix(state["params"])
            new_params, opt = jax.vmap(
                optimizer.update, in_axes=(0, 0, 0, None)
            )(g_l, state["opt"], mixed, lr)
            out = {"params": new_params, "opt": opt,
                   "step": state["step"] + 1}

        if strategy.stale:
            out["prev_params"] = state["params"]
        return out, metrics

    return step


@pytest.mark.parametrize("name", ["sc_psgd_replicated", "sd_psgd",
                                  "ad_psgd", "downpour", "bmuf", "hring"])
def test_golden_bit_identical_to_pre_substrate_step(name):
    """wire=f32 / default topology: the refactored step reproduces the
    pre-substrate update trajectory EXACTLY (34 steps crosses two BMUF
    block boundaries)."""
    s = ST.get_strategy(name)
    L = 4
    params = {"w": jax.random.normal(jax.random.PRNGKey(3), (L, 8))}
    state_new = ST.init_state(s, jax.tree.map(jnp.copy, params), sgd())
    state_old = ST.init_state(s, jax.tree.map(jnp.copy, params), sgd())
    step_new = jax.jit(ST.make_train_step(s, loss_fn, sgd(), constant(0.1),
                                          n_learners=L))
    step_old = jax.jit(_legacy_step_factory(s, sgd(), constant(0.1), L))
    for k in range(34):
        b = data(k)
        state_new, m_new = step_new(state_new, b)
        state_old, m_old = step_old(state_old, b)
    for key in state_old:
        got = jax.tree.leaves(state_new[key])
        want = jax.tree.leaves(state_old[key])
        for a, b_ in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                          err_msg=f"{name}/{key}")
    np.testing.assert_array_equal(np.asarray(m_new["loss"]),
                                  np.asarray(m_old["loss"]))


def test_golden_sc_psgd_nonreplicated_unchanged():
    """The GSPMD data-parallel path (Eq. 13) takes no substrate: same
    trajectory as a plain value_and_grad SGD loop, both jitted (XLA may
    fuse a jitted program differently from op-by-op execution, so only
    compiled against compiled is bit-exact)."""
    s = ST.get_strategy("sc_psgd")
    params = {"w": jnp.zeros((8,))}
    state = ST.init_state(s, jax.tree.map(jnp.copy, params), sgd())
    step = jax.jit(ST.make_train_step(s, loss_fn, sgd(), constant(0.1)))
    opt = sgd()

    @jax.jit
    def ref_step(p, o, b):
        _, g = jax.value_and_grad(loss_fn)(p, b)
        return opt.update(g, o, p, 0.1)

    ref_p, ref_o = jax.tree.map(jnp.copy, params), opt.init(params)
    for k in range(10):
        b = data(k)
        state, _ = step(state, b)
        ref_p, ref_o = ref_step(ref_p, ref_o, b)
    np.testing.assert_array_equal(np.asarray(state["params"]["w"]),
                                  np.asarray(ref_p["w"]))


# ---------------------------------------------------------------------------
# wire codecs
# ---------------------------------------------------------------------------

def test_int8_codec_per_sender_bound():
    rng = np.random.default_rng(0)
    # wildly different per-sender scales: per-sender coding must bound the
    # error by each sender's own amax/254, not the global one
    x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32) \
        * jnp.asarray([[0.01], [1.0], [100.0], [0.5]])
    d = decode_payload("int8", x)
    amax = np.abs(np.asarray(x)).max(axis=1, keepdims=True)
    assert np.all(np.abs(np.asarray(d - x)) <= amax / 254.0 + 1e-7)


def test_bf16_codec_is_truncation():
    x = jnp.asarray([[1.0 + 2 ** -10, -3.25]], jnp.float32)
    d = decode_payload("bf16", x)
    np.testing.assert_array_equal(
        np.asarray(d), np.asarray(x.astype(jnp.bfloat16), np.float32))


def test_topk_codec_keeps_largest():
    x = jnp.asarray([[0.1, -5.0, 0.2, 3.0, -0.3, 0.05, 1.0, -0.01]],
                    jnp.float32)
    d = np.asarray(decode_payload("topk", x, topk_frac=0.25))  # k = 2
    assert set(np.nonzero(d[0])[0]) == {1, 3}
    np.testing.assert_array_equal(d[0, [1, 3]], [-5.0, 3.0])


def test_unknown_wire_and_topology_raise():
    with pytest.raises(ValueError, match="unknown wire"):
        Transport(wire="fp8")
    with pytest.raises(ValueError, match="unknown topology"):
        Transport(topology="torus")
    with pytest.raises(ValueError, match="pod_size"):
        Transport(topology="hierarchical", pod_size=3).make_mixer(8)
    with pytest.raises(ValueError, match="power-of-2"):
        Transport(topology="exp").make_mixer(6)


# ---------------------------------------------------------------------------
# topologies
# ---------------------------------------------------------------------------

def test_hierarchical_equals_kron_matrix():
    L, p = 8, 2
    rng = np.random.default_rng(1)
    w = {"a": jnp.asarray(rng.normal(size=(L, 7)), jnp.float32)}
    T = mixing.hierarchical_matrix(L, p)
    assert mixing.is_doubly_stochastic(T)
    ref = mixing.mix_matrix(w, T)["a"]
    fast = mixing.mix_hierarchical(w, pod_size=p)["a"]
    np.testing.assert_allclose(np.asarray(fast), np.asarray(ref), atol=1e-5)
    # the transport's coded path at f32 agrees too
    via_t, _ = Transport(topology="hierarchical", pod_size=p,
                         bucket_bytes=8).make_mixer(L)(w, jnp.int32(0), {})
    np.testing.assert_allclose(np.asarray(via_t["a"]), np.asarray(ref),
                               atol=1e-5)


def test_hierarchical_degenerations():
    rng = np.random.default_rng(2)
    w = {"a": jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)}
    ring = Transport(topology="hierarchical", pod_size=1).make_mixer(6)
    np.testing.assert_array_equal(
        np.asarray(ring(w, jnp.int32(0), {})[0]["a"]),
        np.asarray(mixing.mix_ring(w)["a"]))
    uni = Transport(topology="hierarchical", pod_size=6).make_mixer(6)
    np.testing.assert_array_equal(
        np.asarray(uni(w, jnp.int32(0), {})[0]["a"]),
        np.asarray(mixing.mix_uniform(w)["a"]))


def test_bucketed_collectives_match_fused():
    """Bucketing only chunks the payload; elementwise codecs + combines
    give identical results (f32/bf16 exactly; int8 re-scales per bucket)."""
    rng = np.random.default_rng(3)
    w = {"a": jnp.asarray(rng.normal(size=(4, 1000)), jnp.float32)}
    for wire in ("f32", "bf16"):
        fused, _ = Transport(topology="ring", wire=wire).make_mixer(4)(
            w, jnp.int32(0), {})
        bucketed, _ = Transport(topology="ring", wire=wire,
                                bucket_bytes=256).make_mixer(4)(
            w, jnp.int32(0), {})
        np.testing.assert_array_equal(np.asarray(fused["a"]),
                                      np.asarray(bucketed["a"]))
    exact = mixing.mix_ring(w)["a"]
    q8, _ = Transport(topology="ring", wire="int8",
                      bucket_bytes=256).make_mixer(4)(w, jnp.int32(0), {})
    scale = float(jnp.max(jnp.abs(w["a"])))
    assert float(jnp.max(jnp.abs(q8["a"] - exact))) < scale / 100


def test_mean_preservation_across_wires():
    """Doubly-stochastic mixing preserves the replica mean; coded wires
    must stay within their codec error (exactly, for difference-coded
    topk: the gossip term T·ŵ − ŵ sums to zero)."""
    rng = np.random.default_rng(4)
    w = {"a": jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)}
    mu = np.asarray(w["a"]).mean(axis=0)
    for topo in ("ring", "uniform", "exp"):
        for wire in ("f32", "bf16", "int8", "topk"):
            t = Transport(topology=topo, wire=wire, topk_frac=0.25)
            comm = t.init_comm(w)
            mixed, _ = t.make_mixer(8)(w, jnp.int32(0), comm)
            drift = np.abs(np.asarray(mixed["a"]).mean(axis=0) - mu).max()
            tol = {"f32": 1e-6, "bf16": 2e-2, "int8": 2e-2,
                   "topk": 1e-5}[wire]
            assert drift < tol, (topo, wire, drift)


# ---------------------------------------------------------------------------
# composability: compressed wires under sc/ad_psgd + bmuf (acceptance)
# ---------------------------------------------------------------------------

def _run(name, transport, steps=400, lr=0.04, L=4):
    s = ST.get_strategy(name)
    params = ST.stack_for_learners({"w": jnp.zeros((8,))}, L)
    state = ST.init_state(s, params, sgd(), transport=transport)
    step = jax.jit(ST.make_train_step(s, loss_fn, sgd(), constant(lr),
                                      n_learners=L, transport=transport))
    for k in range(steps):
        state, m = step(state, data(k))
    final = ST.average_learners(state["params"])
    heldout = float(loss_fn(final, data(10_000)))
    return final, heldout, state, m


@pytest.mark.parametrize("strat", ["sc_psgd_replicated", "ad_psgd", "bmuf"])
@pytest.mark.parametrize("wire", ["int8", "topk"])
def test_compressed_wire_matches_f32_final_loss(strat, wire):
    topo = ST.get_strategy(strat).topology
    t_f32 = Transport(topology=topo, wire="f32")
    t_c = Transport(topology=topo, wire=wire, topk_frac=0.25)
    _, held_f32, _, _ = _run(strat, t_f32)
    final, held_c, state, m = _run(strat, t_c)
    # same optimum within tolerance (bmuf converges more slowly on the
    # toy, but identically so across wires)
    assert abs(held_c - held_f32) < 0.05, (held_c, held_f32)
    assert float(m["wire_bytes"]) >= 0.0
    if wire == "topk":
        assert set(state["comm"]) == {"residual", "estimate"}


def test_hring_mixed_intra_inter_wires_converge():
    """The paper's §V setting: cheap bf16 inside the pod, topk-sparse
    across pods."""
    t = Transport(topology="hierarchical", pod_size=2, intra_wire="bf16",
                  wire="topk", topk_frac=0.25)
    final, held, _, m = _run("hring", t)
    assert float(jnp.linalg.norm(final["w"] - W_TRUE)) < 0.1
    assert float(m["wire_bytes"]) > 0


def test_bmuf_wire_bytes_only_on_sync_steps():
    t = Transport(topology="uniform", wire="int8")
    s = ST.get_strategy("bmuf")
    params = ST.stack_for_learners({"w": jnp.zeros((8,))}, 4)
    state = ST.init_state(s, params, sgd(), transport=t)
    step = jax.jit(ST.make_train_step(s, loss_fn, sgd(), constant(0.03),
                                      n_learners=4, transport=t))
    wb = []
    for k in range(2 * s.block_size):
        state, m = step(state, data(k))
        wb.append(float(m["wire_bytes"]))
    assert wb.count(0.0) == len(wb) - 2          # two block boundaries
    assert wb[s.block_size - 1] > 0 and wb[-1] > 0


def test_topk_without_comm_state_raises():
    t = Transport(topology="ring", wire="topk")
    w = {"a": jnp.ones((4, 8))}
    with pytest.raises(ValueError, match="error-feedback state"):
        t.make_mixer(4)(w, jnp.int32(0), {})


# ---------------------------------------------------------------------------
# error-feedback residuals: f32 accumulation + bounded drift (satellite)
# ---------------------------------------------------------------------------

def test_ef_residuals_accumulate_in_f32_under_bf16_params():
    """100 mixing rounds on bf16 replicas: the residual/estimate trees
    stay f32, consensus is reached, and the replica mean drifts only by
    bf16 storage rounding — the compression itself leaks nothing."""
    rng = np.random.default_rng(5)
    w = {"a": jnp.asarray(rng.normal(size=(4, 256)),
                          jnp.float32).astype(jnp.bfloat16)}
    mu0 = np.asarray(w["a"], np.float32).mean(axis=0)
    t = Transport(topology="ring", wire="topk", topk_frac=0.1)
    comm = t.init_comm(w)
    mix = jax.jit(t.make_mixer(4))
    start = float(ST.consensus_distance(w))
    for k in range(100):
        w, comm = mix(w, jnp.int32(k), comm)
    assert comm["residual"]["a"].dtype == jnp.float32
    assert comm["estimate"]["a"].dtype == jnp.float32
    assert w["a"].dtype == jnp.bfloat16
    end = float(ST.consensus_distance(w))
    assert end < 0.05 * start                      # gossip converged
    drift = np.abs(np.asarray(w["a"], np.float32).mean(axis=0) - mu0).max()
    # bf16 ulp-scale storage rounding over 100 rounds, nothing more
    assert drift < 0.05, drift
    # the estimate tracks the (bf16) replicas to codec accuracy
    est_err = np.abs(np.asarray(comm["estimate"]["a"])
                     - np.asarray(w["a"], np.float32)).max()
    assert est_err < 0.1, est_err


def test_ef_residual_shapes_follow_payload_domain():
    """Hierarchical inter-pod residuals live at pod granularity."""
    w = {"a": jnp.ones((8, 16))}
    t = Transport(topology="hierarchical", pod_size=2, wire="topk")
    comm = t.init_comm(w)
    assert comm["residual"]["a"].shape == (4, 16)   # one per pod
    assert comm["estimate"]["a"].shape == (4, 16)


def test_topk_intra_wire_rejected():
    """Difference-coded wires are gossip-only: an allreduce stage cannot
    realize the damped-estimate update (undamped, the first round would
    collapse every pod to ~topk_frac of its mass)."""
    with pytest.raises(ValueError, match="gossip-only"):
        Transport(topology="hierarchical", pod_size=2, intra_wire="topk")


def test_lossy_intra_wire_not_swallowed_by_f32_fast_path():
    """Regression: wire='f32' + intra_wire='bf16' must actually code the
    intra-pod stage (the fast path used to shortcut to the exact mixer
    while wire_bytes still billed the bf16 payload)."""
    rng = np.random.default_rng(6)
    w = {"a": jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)}
    t = Transport(topology="hierarchical", pod_size=2, intra_wire="bf16")
    mixed, _ = t.make_mixer(8)(w, jnp.int32(0), {})
    exact = mixing.mix_hierarchical(w, pod_size=2)["a"]
    diff = float(jnp.max(jnp.abs(mixed["a"] - exact)))
    assert diff > 0.0                      # the codec really ran
    assert diff < 2e-2                     # ...and is only bf16 rounding
    # pod_size=1 has no intra stage: the exact fast path is still taken
    t1 = Transport(topology="hierarchical", pod_size=1, intra_wire="bf16")
    m1, _ = t1.make_mixer(8)(w, jnp.int32(0), {})
    np.testing.assert_array_equal(np.asarray(m1["a"]),
                                  np.asarray(mixing.mix_ring(w)["a"]))


# ---------------------------------------------------------------------------
# wire-byte accounting (acceptance: int8 <= 0.27x f32)
# ---------------------------------------------------------------------------

def test_wire_bytes_ratios_on_blstm_param_tree():
    from repro.configs import get_arch
    from repro.models import build_model

    L = 16
    specs = build_model(get_arch("swb2000-blstm").reduced()).param_specs()
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((L,) + tuple(s.shape), jnp.float32),
        specs)
    per = {w: Transport(topology="ring", wire=w).wire_bytes(stacked)
           for w in ("f32", "bf16", "int8", "topk")}
    assert per["int8"] <= 0.27 * per["f32"]
    assert per["topk"] < per["int8"] < per["bf16"] < per["f32"]
    assert per["bf16"] == 0.5 * per["f32"]


def test_wire_bytes_topology_multipliers():
    w = {"a": jnp.ones((8, 100))}
    f32 = 400.0
    assert Transport(topology="ring").wire_bytes(w) == 2 * f32
    assert Transport(topology="uniform").wire_bytes(w) == \
        pytest.approx(2 * 7 / 8 * f32)
    assert Transport(topology="exp").wire_bytes(w) == f32
    assert Transport(topology="none").wire_bytes(w) == 0.0
    # hierarchical: intra 2(p-1)/p + inter ring amortized over the pod
    h = Transport(topology="hierarchical", pod_size=2)
    assert h.wire_bytes(w) == pytest.approx(2 * 0.5 * f32 + 2 * f32 / 2)
    # alone in the ring -> silence
    assert Transport(topology="ring").wire_bytes({"a": jnp.ones((1, 9))}) \
        == 0.0


def test_transport_from_cfg_resolution():
    from repro.configs import get_arch

    cfg = dataclasses.replace(get_arch("swb2000-blstm"),
                              comm_wire="int8", comm_bucket_mb=4,
                              comm_pod_size=2)
    t = ST.transport_from_cfg(cfg, ST.get_strategy("hring"))
    assert t == Transport(topology="hierarchical", wire="int8",
                          bucket_bytes=4 * 2 ** 20, pod_size=2,
                          topk_frac=cfg.comm_topk_frac)
    t2 = ST.transport_from_cfg(get_arch("swb2000-blstm"),
                               ST.get_strategy("ad_psgd_q8"))
    assert (t2.topology, t2.wire) == ("ring", "int8")
