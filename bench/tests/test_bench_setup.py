"""The set-up readers (``setup.step_trace_lower_s``,
``setup.step_compile_load_s``): on made-up compile records, and in a
traced run of a tiny copy of the BLSTM cell on the CPU."""
import time

import pytest

import tiny
from harness import compiles, spec, trace
from harness.train_cell import Context

MS = 1_000_000
READERS = ("setup.step_trace_lower_s", "setup.step_compile_load_s")


def _ctx(ops, spans, chips):
    return Context(trace=trace.from_events(ops, spans), chips=chips,
                   peak={}, model_flops_per_step=0.0, mosaic={}, host={})


def _reader(name):
    return spec.load_module(tiny.BENCH / "metrics" / f"{name}.py", "metrics")


def _rec(phase, seconds, start):
    return {"fn": "train_step", "phase": phase, "seconds": seconds,
            "start": start, "end": start + seconds}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced tiny run, the counter's step records right after it, and
    the run's wall interval on ``time.time()`` (the records' clock)."""
    root = tiny.make_root(tmp_path_factory.mktemp("tiny"))
    t0 = time.time()
    r = tiny.run_cell(root, traced=True)
    return r, compiles.step_records(), (t0, time.time())


def test_traced_run_reports_the_setup_metrics(traced):
    r, recs, (t0, t1) = traced
    assert r["correct"], r["checks"]
    for name in READERS:
        assert r["metrics"][name]["value"] > 0, name
    # the group is this run's own compile, and takes part of its time
    group = compiles.setup_group(recs)
    assert t0 <= group[0]["start"] and group[2]["end"] <= t1
    assert (r["metrics"]["setup.step_trace_lower_s"]["value"]
            + r["metrics"]["setup.step_compile_load_s"]["value"] < t1 - t0)


def test_setup_group_is_the_first_compile_of_the_step(traced):
    """In a traced run the harness lowers the step again after the
    window: jax answers it from its caches, so the counter keeps at most
    traces of the same step under a millisecond after the set-up
    group, which the set-up readers pass over."""
    _, recs, _ = traced
    group = compiles.setup_group(recs)
    assert [r["phase"] for r in group] == ["trace", "lower", "compile"]
    later = recs[recs.index(group[2]) + 1:]
    assert all(r["phase"] == "trace"
               and r["seconds"] < compiles.MIN_TRACE_S for r in later)


def test_setup_readers_take_the_latest_full_trace_of_the_step(monkeypatch):
    log = [
        # an earlier cell's set-up in the same process
        _rec("trace", 4.0, 0.0), _rec("lower", 2.0, 4.0),
        _rec("compile", 9.0, 6.0),
        # this cell's set-up: trace, lowering, persistent-cache load
        _rec("trace", 3.0, 20.0), _rec("lower", 1.5, 23.0),
        _rec("compile", 2.5, 24.5),
        # a call that missed the fast path and the lowering after a
        # traced window: jax answers both traces from its cache
        _rec("trace", 8e-5, 30.0), _rec("trace", 7e-5, 90.0),
    ]
    monkeypatch.setattr(compiles, "step_records", lambda: list(log))
    ctx = _ctx({}, [("bench/window", 0, 10 * MS)], 1)
    assert _reader("setup.step_trace_lower_s").read(ctx) == 4.5
    assert _reader("setup.step_compile_load_s").read(ctx) == 2.5
    # a fresh trace that lowered nothing yet does not hide the set-up
    log.append(_rec("trace", 2.0, 95.0))
    assert _reader("setup.step_trace_lower_s").read(ctx) == 4.5
    # were the lowering after the window not cached, the group would
    # still be the set-up's
    log[-1:] = [_rec("lower", 1.4, 90.1), _rec("compile", 2.0, 91.5)]
    assert _reader("setup.step_trace_lower_s").read(ctx) == 4.5


@pytest.mark.parametrize("recs", [None, [], "untraced"],
                         ids=["no_counter", "no_records", "never_traced"])
def test_setup_readers_give_nothing_without_a_setup_group(monkeypatch, recs):
    if recs == "untraced":
        recs = [_rec("lower", 1.4, 90.1), _rec("compile", 2.0, 91.5),
                _rec("trace", 7e-5, 95.0)]
    monkeypatch.setattr(compiles, "step_records", lambda: recs)
    ctx = _ctx({}, [("bench/window", 0, 10 * MS)], 1)
    for name in READERS:
        assert _reader(name).read(ctx) is None, name
