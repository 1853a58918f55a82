"""Fault injection + elastic membership (docs/fault_tolerance.md).

Covers the three layers of the resilience stack: the deterministic
FaultPlan schedule, the elastic mixing matrices (doubly stochastic over
any live set), and the elastic train step (parity with the plain step
when nothing fails; convergence and frozen-dead-learner semantics when
things do)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mixing
from repro.core import strategies as ST
from repro.core.faults import (Departure, FaultPlan, Straggler,
                               parse_departures, parse_stragglers)
from repro.core.transport import Transport
from repro.optim.optimizers import momentum, sgd
from repro.optim.schedules import constant

W_TRUE = jax.random.normal(jax.random.PRNGKey(7), (8,))


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def data(seed, n=64):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 8))
    return {"x": x, "y": x @ W_TRUE}


# ---------------------------------------------------------------------------
# FaultPlan: deterministic, validated, serializable
# ---------------------------------------------------------------------------

def test_fault_plan_deterministic_and_serializable():
    plan = FaultPlan(8, seed=3, stragglers=(Straggler(0, 4),),
                     departures=(Departure(1, 30, 60),),
                     drop_prob=0.2, stall_prob=0.05,
                     corrupt_prob=0.1, corrupt_scale=0.05)
    twin = FaultPlan.from_dict(plan.to_dict())
    for step in (0, 7, 31, 60, 200):
        a, b = plan.step_inputs(step), twin.step_inputs(step)
        assert set(a) == {"active", "contrib", "rejoin", "edge_ok",
                          "corrupt"}
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    # a different seed changes the stochastic parts
    other = FaultPlan.from_dict({**plan.to_dict(), "seed": 4})
    assert any(
        not np.array_equal(plan.step_inputs(s)["edge_ok"],
                           other.step_inputs(s)["edge_ok"])
        for s in range(10))


def test_fault_plan_schedules():
    plan = FaultPlan(8, stragglers=(Straggler(0, 4, phase=0),),
                     departures=(Departure(1, 30, 60), Departure(2, 50)))
    # straggler contributes only every 4th step
    assert plan.step_inputs(4)["contrib"][0] == 1.0
    assert plan.step_inputs(5)["contrib"][0] == 0.0
    # crash window [30, 60); learner 2 never returns
    assert plan.step_inputs(29)["active"][1] == 1.0
    assert plan.step_inputs(30)["active"][1] == 0.0
    assert plan.step_inputs(60)["active"][1] == 1.0
    assert plan.step_inputs(60)["rejoin"][1] == 1.0
    assert plan.step_inputs(59)["rejoin"][1] == 0.0
    assert plan.step_inputs(500)["active"][2] == 0.0


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="ZERO active"):
        FaultPlan(2, departures=(Departure(0, 5), Departure(1, 5)))
    with pytest.raises(ValueError, match="rejoin"):
        FaultPlan(2, departures=(Departure(0, 5, 5),))
    with pytest.raises(ValueError, match="out of range"):
        FaultPlan(2, stragglers=(Straggler(5, 2),))
    with pytest.raises(ValueError, match="drop_prob"):
        FaultPlan(2, drop_prob=1.5)
    # staggered departures with rejoins are fine
    FaultPlan(2, departures=(Departure(0, 5, 10), Departure(1, 10, 15)))


def test_fault_plan_edge_ok_symmetric():
    plan = FaultPlan(8, drop_prob=0.3)
    eo = plan.step_inputs(3)["edge_ok"]
    np.testing.assert_array_equal(eo, eo.T)
    np.testing.assert_array_equal(np.diag(eo), np.ones(8))
    assert (eo == 0).any()   # at p=0.3 over 28 edges this is near-certain


def test_fault_spec_parsers():
    assert parse_stragglers("0:4, 3:2") == (Straggler(0, 4),
                                            Straggler(3, 2))
    assert parse_departures("1:30:60,2:50") == (Departure(1, 30, 60),
                                                Departure(2, 50, -1))
    assert parse_stragglers("") == ()
    with pytest.raises(ValueError, match="straggler"):
        parse_stragglers("0:4:9")
    with pytest.raises(ValueError, match="departure"):
        parse_departures("1")


# ---------------------------------------------------------------------------
# Elastic mixing matrices
# ---------------------------------------------------------------------------

MASKS = [np.ones(8, np.float32),
         np.array([1, 0, 1, 1, 1, 1, 0, 1], np.float32),
         np.array([1, 1, 0, 0, 0, 0, 0, 0], np.float32),
         np.array([1, 0, 0, 0, 0, 0, 0, 0], np.float32)]


@pytest.mark.parametrize("topology", ["ring", "uniform", "exp",
                                      "hierarchical", "none"])
def test_elastic_matrix_doubly_stochastic_and_freezes_dead(topology):
    for mask in MASKS:
        T = np.asarray(mixing.elastic_matrix(mask, topology, step=3,
                                             pod_size=4))
        assert mixing.is_doubly_stochastic(T, atol=1e-4), (topology, mask)
        for i in np.where(mask == 0)[0]:     # dead learners are identity
            e = np.zeros(8)
            e[i] = 1
            np.testing.assert_allclose(T[i], e, atol=1e-5)
            np.testing.assert_allclose(T[:, i], e, atol=1e-4)


def test_elastic_matrices_match_static_when_all_active():
    ones = np.ones(8, np.float32)
    np.testing.assert_allclose(np.asarray(mixing.elastic_ring_matrix(ones)),
                               mixing.ring_matrix(8), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mixing.elastic_uniform_matrix(ones)),
        mixing.uniform_matrix(8), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(mixing.elastic_hierarchical_matrix(ones, 4)),
        mixing.hierarchical_matrix(8, 4), atol=1e-5)


def test_elastic_ring_two_survivors_degenerate():
    """Two survivors of eight reproduce the L=2 ring [2/3, 1/3] case."""
    T = np.asarray(mixing.elastic_ring_matrix(
        np.array([1, 0, 0, 1, 0, 0, 0, 0], np.float32)))
    assert T[0, 3] == pytest.approx(1 / 3)
    assert T[0, 0] == pytest.approx(2 / 3)


def test_elastic_exp_consensus_over_survivors():
    """4 live of 8: two exp rounds reach exact consensus (hypercube)."""
    mask = np.array([1, 1, 0, 1, 0, 0, 1, 0], np.float32)
    P = (np.asarray(mixing.elastic_exp_matrix(mask, 1))
         @ np.asarray(mixing.elastic_exp_matrix(mask, 0)))
    live = mask == 1
    np.testing.assert_allclose(P[np.ix_(live, live)],
                               np.full((4, 4), 0.25), atol=1e-5)


def test_staleness_damping_downweights_and_stays_ds():
    s = np.array([0, 5, 0, 0, 0, 0, 0, 0], np.float32)
    T = np.asarray(mixing.elastic_matrix(np.ones(8, np.float32), "ring",
                                         staleness=s,
                                         staleness_lambda=0.5))
    base = np.asarray(mixing.ring_matrix(8))
    assert mixing.is_doubly_stochastic(T)
    assert T[0, 1] < base[0, 1]          # stale learner's influence damped
    assert T[2, 1] < base[2, 1]
    assert T[4, 5] == pytest.approx(base[4, 5], abs=1e-6)  # fresh untouched
    # λ = 0 is the identity transform
    T0 = np.asarray(mixing.elastic_matrix(np.ones(8, np.float32), "ring",
                                          staleness=s, staleness_lambda=0.0))
    np.testing.assert_allclose(T0, base, atol=1e-6)


def test_edge_mask_drops_and_stays_ds():
    eo = np.ones((8, 8), np.float32)
    eo[0, 1] = eo[1, 0] = 0
    T = np.asarray(mixing.elastic_matrix(np.ones(8, np.float32), "ring",
                                         edge_ok=eo))
    assert T[0, 1] == 0 and T[1, 0] == 0
    assert mixing.is_doubly_stochastic(T)


# ---------------------------------------------------------------------------
# Elastic train step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["sd_psgd", "ad_psgd",
                                  "sc_psgd_replicated", "downpour",
                                  "hring", "bmuf"])
def test_elastic_step_matches_plain_without_faults(name):
    """With everyone active and contributing, the step built with a
    fault plan walks the plan-less step's trajectory (matrix contraction
    vs rolls: f32 matmul tolerance, not bit-exact).  exp is excluded by
    design — its elastic matrix is the symmetrized one-peer graph
    (transport docstring)."""
    s = ST.get_strategy(name)
    L = 8
    params = ST.stack_for_learners({"w": jnp.zeros((8,))}, L)
    tr = ST.default_transport(s)
    plan = FaultPlan(L)
    st_p = ST.init_state(s, params, sgd(), tr)
    st_e = ST.init_state(s, params, sgd(), tr, fault_plan=plan)
    plain = jax.jit(ST.make_train_step(s, loss_fn, sgd(), constant(0.05),
                                       n_learners=L, transport=tr))
    el = jax.jit(ST.make_train_step(
        s, loss_fn, sgd(), constant(0.05), n_learners=L, transport=tr,
        fault_plan=plan))
    for k in range(40):
        st_p, _ = plain(st_p, data(k))
        st_e, m = el(st_e, data(k), plan.step_inputs(k))
    np.testing.assert_allclose(np.asarray(st_e["params"]["w"]),
                               np.asarray(st_p["params"]["w"]), atol=2e-5)
    assert float(m["n_active"]) == L
    assert int(m["staleness_max"]) == 0


def test_elastic_converges_under_straggler_and_crash():
    """The acceptance-criteria fault plan at test scale: 1 of 8
    straggling 4×, one crash/rejoin — AD-PSGD with staleness-aware
    mixing still reaches the optimum, the dead learner's replica is
    frozen bit-for-bit, and the rejoiner re-enters at the survivors'
    consensus."""
    L = 8
    plan = FaultPlan(L, stragglers=(Straggler(0, 4),),
                     departures=(Departure(1, 30, 60),))
    s = ST.get_strategy("ad_psgd")
    tr = Transport(topology="ring", staleness_lambda=0.2)
    params = ST.stack_for_learners({"w": jnp.zeros((8,))}, L)
    state = ST.init_state(s, params, sgd(), tr, fault_plan=plan)
    el = jax.jit(ST.make_train_step(
        s, loss_fn, sgd(), constant(0.05), n_learners=L, transport=tr,
        with_consensus=True, fault_plan=plan))
    for k in range(300):
        before = np.asarray(state["params"]["w"][1])
        state, m = el(state, data(k), {kk: jnp.asarray(v) for kk, v in
                                       plan.step_inputs(k).items()})
        if 31 <= k < 60:                 # dead: frozen bit-for-bit
            np.testing.assert_array_equal(
                np.asarray(state["params"]["w"][1]), before)
        if k == 60:                      # rejoined at incumbents' mean
            assert not np.array_equal(
                np.asarray(state["params"]["w"][1]), before)
    final = ST.average_learners(state["params"])
    assert float(jnp.linalg.norm(final["w"] - W_TRUE)) < 0.05
    assert float(m["consensus"]) < 0.05
    assert np.isfinite(float(m["loss"]))


def test_elastic_converges_with_drops_corruption_bf16():
    """Wire-level weather (bf16 codec + dropped edges + corrupted
    payloads) with a momentum optimizer still converges near the
    optimum — corruption only ever poisons the peer view."""
    L = 8
    plan = FaultPlan(L, seed=3, stragglers=(Straggler(2, 2),),
                     drop_prob=0.1, corrupt_prob=0.1, corrupt_scale=0.05)
    s = ST.get_strategy("ad_psgd")
    tr = Transport(topology="ring", wire="bf16", staleness_lambda=0.1)
    params = ST.stack_for_learners({"w": jnp.zeros((8,))}, L)
    state = ST.init_state(s, params, momentum(), tr, fault_plan=plan)
    el = jax.jit(ST.make_train_step(
        s, loss_fn, momentum(), constant(0.02), n_learners=L, transport=tr,
        fault_plan=plan))
    for k in range(300):
        state, m = el(state, data(k), {kk: jnp.asarray(v) for kk, v in
                                       plan.step_inputs(k).items()})
    final = ST.average_learners(state["params"])
    assert float(jnp.linalg.norm(final["w"] - W_TRUE)) < 0.15


def test_elastic_staleness_counters_track_stragglers():
    L = 4
    plan = FaultPlan(L, stragglers=(Straggler(0, 4),))
    s = ST.get_strategy("sd_psgd")
    state = ST.init_state(s, ST.stack_for_learners(
        {"w": jnp.zeros((8,))}, L), sgd(), fault_plan=plan)
    el = jax.jit(ST.make_train_step(
        s, loss_fn, sgd(), constant(0.05), n_learners=L, fault_plan=plan))
    for k in range(6):
        state, m = el(state, data(k), {kk: jnp.asarray(v) for kk, v in
                                       plan.step_inputs(k).items()})
    # steps 0..5: learner 0 contributed at 0 and 4 only -> staleness 1
    # after step 5 (k=5 missed); fresh learners at 0
    st = np.asarray(state["staleness"])
    assert st[0] == 1 and (st[1:] == 0).all()
    assert int(m["n_contrib"]) == 3


def test_setup_training_fault_plan_is_the_only_switch():
    """``setup_training(fault_plan=...)`` is the seam between the two
    steps: a tiny BLSTM AD-PSGD job with an everyone-active plan walks
    the plan-less job's trajectory, and the plan-less job keeps the
    plain state and the two-argument step."""
    import dataclasses

    from repro.configs import get_arch
    from repro.data import make_dataset
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import setup_training

    L = 2
    cfg = dataclasses.replace(
        get_arch("swb2000-blstm").reduced(), n_layers=1, lstm_hidden=16,
        lstm_bottleneck=8, input_dim=8, vocab=16, param_dtype="float32")
    mesh = make_local_mesh(devices=jax.devices()[:1])
    plan = FaultPlan(L)
    ds = make_dataset(cfg, seq_len=5, batch=4, seed=0)
    jobs = {name: setup_training(cfg, mesh, strategy_name="ad_psgd",
                                 n_learners=L, fault_plan=p)
            for name, p in (("plain", None), ("plan", plan))}
    assert "staleness" not in jobs["plain"][0]
    assert "staleness" in jobs["plan"][0]
    plain_state, plain_step, _ = jobs["plain"]
    plan_state, plan_step, _ = jobs["plan"]
    with jax.set_mesh(mesh):
        for k in range(20):
            batch = ds.batch_at(k)
            plain_state, _ = plain_step(plain_state, batch)
            plan_state, m = plan_step(plan_state, batch,
                                      plan.step_inputs(k))
    for a, b in zip(jax.tree.leaves(plain_state["params"]),
                    jax.tree.leaves(plan_state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert float(m["n_active"]) == L


# ---------------------------------------------------------------------------
# Guards (the all-inactive edge and unsupported configurations)
# ---------------------------------------------------------------------------

def test_check_active_and_split_guard():
    with pytest.raises(ValueError, match="no active learners"):
        ST.check_active(np.zeros(4))
    assert ST.check_active(np.array([0, 1, 0, 1])) == 2
    with pytest.raises(ValueError, match="empty learner set"):
        ST.split_learner_batch({"x": jnp.zeros((8, 2))}, 0)


def test_elastic_rejects_topk_and_non_replicated():
    with pytest.raises(ValueError, match="difference-coded"):
        Transport(topology="ring", wire="topk").make_elastic_mixer(8)
    with pytest.raises(ValueError, match="not replicated"):
        ST.make_train_step(ST.get_strategy("sc_psgd"), loss_fn, sgd(),
                           constant(0.1), n_learners=1,
                           fault_plan=FaultPlan(1))
