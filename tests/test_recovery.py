"""Crash-recovery contract (docs/fault_tolerance.md).

* Atomic saves: no partially-written ``step_<n>`` ever exists under its
  final name; pruning happens only after the new step is durable.
* Validated restores: structure / per-leaf shape / per-leaf dtype
  mismatches raise ValueErrors naming the offending leaf path.
* Bit-exact resume: save→restore round-trips every bit (bf16 params,
  optimizer moments, topk error-feedback residuals), and a killed-and-
  resumed run matches the uninterrupted run step-for-step — for every
  strategy that carries comm state, and through the real CLI under an
  active fault plan.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as CK
from repro.core import strategies as ST
from repro.core.faults import Departure, FaultPlan, Straggler
from repro.core.transport import Transport
from repro.optim.optimizers import momentum, sgd
from repro.optim.schedules import constant

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_env(tmp_path_factory):
    """The CLIs keep their compile cache under pytest's tmp dir, not in
    the checkout."""
    ENV["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax_cache"))

W_TRUE = jax.random.normal(jax.random.PRNGKey(7), (8,))


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"].astype(jnp.float32)
    return jnp.mean((pred - batch["y"]) ** 2)


def data(seed, n=64):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 8))
    return {"x": x, "y": x @ W_TRUE}


def _assert_trees_bitwise_equal(a, b):
    for pa, (la, lb) in zip(
            jax.tree_util.tree_flatten_with_path(a)[0],
            zip(jax.tree.leaves(a), jax.tree.leaves(b))):
        name = jax.tree_util.keystr(pa[0])
        xa, xb = np.asarray(la), np.asarray(lb)
        assert xa.dtype == xb.dtype, name
        np.testing.assert_array_equal(
            xa.view(np.uint16) if xa.dtype.name == "bfloat16" else xa,
            xb.view(np.uint16) if xb.dtype.name == "bfloat16" else xb,
            err_msg=name)


# ---------------------------------------------------------------------------
# Atomicity + pruning
# ---------------------------------------------------------------------------

def test_save_layout_atomic_and_prune_after_durable(tmp_path):
    d = str(tmp_path / "ck")
    state = {"w": jnp.arange(4.0), "step": jnp.int32(0)}
    for s in (5, 6, 7, 8):
        path = CK.save(d, s, state, keep=2)
        assert os.path.basename(path) == f"step_{s}"
        assert {"tree.msgpack", "arrays.npz"} <= set(os.listdir(path))
        # no temp staging dir survives a completed save
        assert not [f for f in os.listdir(d) if f.startswith(".tmp_")]
    # keep=2 -> only the two newest remain, pruned after each durable save
    assert sorted(CK.latest_steps(d)) == [7, 8]
    assert CK.latest_step(d) == 8


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no checkpoints"):
        CK.restore(str(tmp_path / "nothing"), {"w": jnp.zeros(2)})


# ---------------------------------------------------------------------------
# Validated restores: every mismatch class names the leaf
# ---------------------------------------------------------------------------

def test_restore_validates_tree_structure(tmp_path):
    d = str(tmp_path / "ck")
    CK.save(d, 0, {"params": {"w": jnp.zeros(4)},
                   "comm": {"residual": jnp.zeros(4)}})
    with pytest.raises(ValueError, match="tree structure mismatch"):
        CK.restore(d, {"params": {"w": jnp.zeros(4)}})


def test_restore_validates_leaf_shape_names_path(tmp_path):
    d = str(tmp_path / "ck")
    CK.save(d, 0, {"params": {"w": jnp.zeros((4, 8))}})
    with pytest.raises(ValueError) as e:
        CK.restore(d, {"params": {"w": jnp.zeros((8, 8))}})
    msg = str(e.value)
    assert "['params']['w']" in msg
    assert "learner count" in msg


def test_restore_validates_leaf_dtype_names_path(tmp_path):
    d = str(tmp_path / "ck")
    CK.save(d, 0, {"params": {"w": jnp.zeros(4, jnp.bfloat16)}})
    with pytest.raises(ValueError) as e:
        CK.restore(d, {"params": {"w": jnp.zeros(4, jnp.float32)}})
    assert "['params']['w']" in str(e.value)
    assert "dtype" in str(e.value)


# ---------------------------------------------------------------------------
# state['comm'] round-trip: topk error-feedback residuals under bf16
# params are bit-exact and the next 10 steps match an uncheckpointed run
# ---------------------------------------------------------------------------

def test_topk_comm_state_roundtrip_bf16_and_next_10_steps(tmp_path):
    s = ST.get_strategy("ad_psgd")
    tr = Transport(topology="ring", wire="topk", topk_frac=0.25)
    L = 4
    params = ST.stack_for_learners({"w": jnp.zeros((8,), jnp.bfloat16)}, L)
    step = jax.jit(ST.make_train_step(s, loss_fn, sgd(), constant(0.05),
                                      n_learners=L, transport=tr))
    state = ST.init_state(s, params, sgd(), tr)
    for k in range(10):
        state, _ = step(state, data(k))
    assert set(state["comm"]) == {"residual", "estimate"}
    # residuals are non-trivial by now (difference coding has history)
    assert float(jnp.abs(state["comm"]["residual"]["w"]).max()) > 0

    CK.save(str(tmp_path), 10, state)
    like = ST.init_state(s, params, sgd(), tr)
    restored, at = CK.restore(str(tmp_path), like)
    assert at == 10
    _assert_trees_bitwise_equal(restored, state)   # incl. EF residuals

    # the next 10 steps from the restored state match the uncheckpointed
    # continuation bit-for-bit
    for k in range(10, 20):
        state, m_live = step(state, data(k))
        restored, m_ck = step(restored, data(k))
        np.testing.assert_array_equal(np.asarray(m_live["loss"]),
                                      np.asarray(m_ck["loss"]))
    _assert_trees_bitwise_equal(restored, state)


# ---------------------------------------------------------------------------
# Kill-and-resume bit-exactness for every strategy with comm state
# ---------------------------------------------------------------------------

COMM_CASES = [
    ("sd_psgd", Transport(topology="ring", wire="topk", topk_frac=0.25)),
    ("ad_psgd", Transport(topology="ring", wire="topk", topk_frac=0.25)),
    ("bmuf", Transport(topology="uniform", wire="topk", topk_frac=0.25)),
    ("hring", Transport(topology="hierarchical", pod_size=2, wire="topk",
                        topk_frac=0.25)),
]


@pytest.mark.parametrize("name,tr", COMM_CASES,
                         ids=[c[0] for c in COMM_CASES])
def test_kill_and_resume_bit_exact(name, tr, tmp_path):
    """Interrupted at step 10 and resumed from the checkpoint, the run
    matches the uninterrupted one step-for-step (losses AND final state,
    bit-for-bit) — optimizer moments and topk EF residuals included."""
    s = ST.get_strategy(name)
    L = 4
    params = ST.stack_for_learners({"w": jnp.zeros((8,))}, L)
    step = jax.jit(ST.make_train_step(s, loss_fn, momentum(),
                                      constant(0.05), n_learners=L,
                                      transport=tr))

    ref = ST.init_state(s, params, momentum(), tr)
    ref_losses = []
    for k in range(20):
        ref, m = step(ref, data(k))
        ref_losses.append(np.asarray(m["loss"]))

    # "crash" after step 10: persist, rebuild from scratch, resume
    state = ST.init_state(s, params, momentum(), tr)
    for k in range(10):
        state, _ = step(state, data(k))
    CK.save(str(tmp_path), 10, state)
    del state
    like = ST.init_state(s, params, momentum(), tr)
    state, at = CK.restore(str(tmp_path), like)
    res_losses = []
    for k in range(at, 20):
        state, m = step(state, data(k))
        res_losses.append(np.asarray(m["loss"]))

    np.testing.assert_array_equal(np.stack(ref_losses[10:]),
                                  np.stack(res_losses))
    _assert_trees_bitwise_equal(state, ref)


def test_elastic_kill_and_resume_bit_exact(tmp_path):
    """Same contract for the elastic step: the checkpoint crosses a
    crash window and a straggler schedule, and the restored run (incl.
    the staleness counters) matches the uninterrupted one bit-for-bit."""
    L = 4
    plan = FaultPlan(L, stragglers=(Straggler(0, 4),),
                     departures=(Departure(1, 6, 14),))
    s = ST.get_strategy("ad_psgd")
    tr = Transport(topology="ring", wire="bf16", staleness_lambda=0.2)
    params = ST.stack_for_learners({"w": jnp.zeros((8,))}, L)
    step = jax.jit(ST.make_train_step(
        s, loss_fn, momentum(), constant(0.05), n_learners=L,
        transport=tr, fault_plan=plan))

    def faults(k):
        return {kk: jnp.asarray(v) for kk, v in plan.step_inputs(k).items()}

    ref = ST.init_state(s, params, momentum(), tr, fault_plan=plan)
    for k in range(20):
        ref, m_ref = step(ref, data(k), faults(k))

    state = ST.init_state(s, params, momentum(), tr, fault_plan=plan)
    for k in range(10):
        state, _ = step(state, data(k), faults(k))
    CK.save(str(tmp_path), 10, state)
    like = ST.init_state(s, params, momentum(), tr, fault_plan=plan)
    state, at = CK.restore(str(tmp_path), like)
    for k in range(at, 20):
        state, m_res = step(state, data(k), faults(k))

    _assert_trees_bitwise_equal(state, ref)
    np.testing.assert_array_equal(np.asarray(m_ref["loss"]),
                                  np.asarray(m_res["loss"]))


# ---------------------------------------------------------------------------
# The real CLI under a fault plan: kill-and-resume reproduces the
# uninterrupted run's final loss exactly (data cursor included)
# ---------------------------------------------------------------------------

def _train(extra, timeout=420):
    args = ["repro.launch.train", "--arch", "swb2000-blstm", "--reduced",
            "--learners", "4", "--strategy", "ad_psgd", "--optimizer",
            "momentum", "--log-every", "7",
            "--comm-staleness-lambda", "0.2",
            "--fault-stragglers", "0:4", "--fault-departures", "1:4:9",
            ] + extra
    return subprocess.run([sys.executable, "-m"] + args, cwd=REPO, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


def _final_loss(stdout):
    lines = [l for l in stdout.splitlines() if l.startswith("final loss")]
    assert lines, stdout[-2000:]
    return lines[-1]


def test_cli_kill_and_resume_under_faults(tmp_path):
    full = _train(["--steps", "14"])
    assert full.returncode == 0, full.stderr[-2000:]
    assert "FaultPlan(L=4" in full.stdout         # banner printed
    assert "act 3/4" in full.stdout               # crash window visible

    ck = str(tmp_path / "ck")
    first = _train(["--steps", "7", "--ckpt-dir", ck, "--ckpt-every", "7"])
    assert first.returncode == 0, first.stderr[-2000:]
    second = _train(["--steps", "14", "--ckpt-dir", ck, "--ckpt-every",
                     "14", "--resume"])
    assert second.returncode == 0, second.stderr[-2000:]
    assert _final_loss(second.stdout) == _final_loss(full.stdout)


def test_cli_resume_without_checkpoint_fails():
    r = _train(["--steps", "2", "--resume", "--ckpt-dir",
                "/tmp/definitely-not-a-ckpt-dir"])
    assert r.returncode != 0
    assert "no checkpoint" in (r.stderr + r.stdout)
