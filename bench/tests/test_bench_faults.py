"""A whole run with the timed path broken underneath (no chip look) must
come out not correct: a step that returns its state unchanged, half of
the batch left out, and the ring exchange left out.  Each fault's numbers
must also fail the limits every cell of the benchmark is held to."""
import jax
import jax.numpy as jnp
import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def _unchanged(step):
    def f(state, batch):
        _, m = step(jax.tree.map(jnp.copy, state), batch)
        return state, m
    return f


def _half_batch(step):
    def f(state, batch):
        return step(state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    return f


def test_unchanged_state_fails(root):
    r = tiny.run_cell(root, wrap_step=_unchanged)
    assert not r["correct"], r["checks"]
    assert tiny.fails_everywhere(r["checks"]), r["checks"]


def test_half_batch_fails(root):
    r = tiny.run_cell(root, wrap_step=_half_batch)
    assert not r["correct"], r["checks"]
    assert tiny.fails_everywhere(r["checks"]), r["checks"]


def test_no_exchange_fails(root, monkeypatch):
    from repro.core import mixing
    monkeypatch.setattr(mixing, "mix_ring", lambda p: p)
    r = tiny.run_cell(root)
    assert not r["correct"], r["checks"]
    assert tiny.fails_everywhere(r["checks"]), r["checks"]
