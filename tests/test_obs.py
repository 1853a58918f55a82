"""Unified observability layer tests (docs/observability.md).

Unit coverage of the metrics registry (deterministic snapshot order,
kind safety, the no-op default), the flight recorder (span nesting,
ring bounding, the JSONL/Chrome exporters and the schema validator),
the compile-vs-steady profiler and the CostModel fit — plus the two
end-to-end contracts: the slo.Recorder-as-view property
(``fold(trace) == live table``) and run-twice JSONL **bit-equality**
of seeded train/serve smokes under ``--trace-deterministic``.
"""
import json
import math
import os
import subprocess
import sys

import pytest

from repro import obs
from repro.obs import (MetricsRegistry, NullRegistry, NOOP, FlightRecorder,
                       NullRecorder, ProfiledFn, chrome_trace,
                       fit_cost_model, nearest_rank, read_jsonl,
                       validate_events, write_jsonl)
from repro.obs.trace import event_to_line

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_env(tmp_path_factory):
    """The CLIs keep their compile cache under pytest's tmp dir, not in
    the checkout."""
    ENV["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax_cache"))


@pytest.fixture(autouse=True)
def _obs_reset():
    """Every test starts and ends on the no-op defaults."""
    obs.reset()
    yield
    obs.reset()


def run(args, timeout=420):
    return subprocess.run([sys.executable, "-m"] + args, cwd=REPO, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_instruments_basic():
    reg = MetricsRegistry()
    c = reg.counter("bytes", strategy="hring")
    c.inc(10)
    c.inc(5)
    assert c.value == 15
    g = reg.gauge("occ")
    g.set(3)
    g.set(7)
    assert g.value == 7
    h = reg.histogram("lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    f = h.fields()
    assert f["count"] == 4 and f["total"] == 10.0 and f["mean"] == 2.5
    assert f["min"] == 1.0 and f["max"] == 4.0
    assert f["p50"] == 2.0 and f["p99"] == 4.0


def test_nearest_rank_convention():
    # matches repro.serving.slo.percentile: ceil(q/100 * n) - 1
    vals = list(range(1, 11))
    assert nearest_rank(vals, 50) == 5
    assert nearest_rank(vals, 95) == 10
    assert math.isnan(nearest_rank([], 50))


def test_same_name_same_tags_same_instrument():
    reg = MetricsRegistry()
    assert reg.counter("x", a=1) is reg.counter("x", a=1)
    assert reg.counter("x", a=1) is not reg.counter("x", a=2)
    assert len(reg) == 2


def test_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_snapshot_order_independent_of_registration():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("z").inc(1)
    a.gauge("a", k="2").set(5)
    a.gauge("a", k="1").set(4)
    b.gauge("a", k="1").set(4)
    b.counter("z").inc(1)
    b.gauge("a", k="2").set(5)
    sa, sb = a.snapshot(), b.snapshot()
    assert sa == sb
    assert [r["name"] for r in sa] == ["a", "a", "z"]
    assert [r["tags"] for r in sa[:2]] == [{"k": "1"}, {"k": "2"}]


def test_null_registry_noop():
    reg = NullRegistry()
    assert reg.counter("x") is NOOP
    assert reg.gauge("x") is NOOP
    assert reg.histogram("x", wall=True) is NOOP
    NOOP.inc()
    NOOP.set(3)
    NOOP.observe(1)
    assert reg.snapshot() == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_span_nesting_parent_ids():
    rec = FlightRecorder()
    with rec.span("outer", step=1):
        with rec.span("inner"):
            pass
        rec.event("mark", x=2)
    evs = rec.events
    # children land before parents (recorded at exit)
    names = [e["name"] for e in evs]
    assert names == ["inner", "mark", "outer"]
    outer = evs[2]
    inner = evs[0]
    assert outer["parent"] == 0
    assert inner["parent"] == outer["id"]
    assert outer["attrs"] == {"step": 1}
    # seq assigned at ENTRY: outer opened first -> lowest seq
    assert outer["seq"] < inner["seq"] < evs[1]["seq"]
    assert outer["dur"] >= inner["dur"] >= 0


def test_ring_bounding_and_n_dropped():
    rec = FlightRecorder(maxlen=10)
    for k in range(25):
        rec.event("e", k=k)
    assert len(rec) == 10
    assert rec.n_dropped == 15
    assert [e["attrs"]["k"] for e in rec.events] == list(range(15, 25))
    rec.clear()
    assert len(rec) == 0 and rec.n_dropped == 0


def test_metric_record_renames_instrument_kind():
    rec = FlightRecorder()
    rec.metric({"name": "lat", "kind": "histogram", "tags": {},
                "wall": False, "count": 3})
    (ev,) = rec.events
    assert ev["kind"] == "metric"          # the event-schema kind
    assert ev["instrument"] == "histogram"  # the registry kind
    assert validate_events([ev]) == []


def test_null_recorder_noop():
    rec = NullRecorder()
    rec.event("x")
    rec.add_span("y", 0.0, 1.0)
    with rec.span("z"):
        pass
    assert len(rec) == 0


# ---------------------------------------------------------------------------
# JSONL export / validation / chrome
# ---------------------------------------------------------------------------

def _sample_events():
    rec = FlightRecorder()
    with rec.span("step", k=1):
        rec.event("mark", v=2.5)
    rec.add_span("jit", 0.5, 0.25, wall=True, phase="compile")
    rec.metric({"name": "loss", "kind": "histogram", "tags": {},
                "wall": False, "count": 1, "mean": 3.0})
    rec.metric({"name": "svc", "kind": "histogram", "tags": {},
                "wall": True, "count": 1, "mean": 0.1})
    return rec.events


def test_jsonl_roundtrip(tmp_path):
    evs = _sample_events()
    path = tmp_path / "t.jsonl"
    n = write_jsonl(evs, str(path))
    assert n == len(evs)
    assert read_jsonl(str(path)) == json.loads(
        json.dumps(evs))  # tuple-free comparison
    assert validate_events(read_jsonl(str(path))) == []


def test_deterministic_export_strips_wall(tmp_path):
    evs = _sample_events()
    path = tmp_path / "d.jsonl"
    write_jsonl(evs, str(path), deterministic=True)
    out = read_jsonl(str(path))
    # wall-marked span AND wall metric dropped; ts/dur stripped
    assert len(out) == len(evs) - 2
    for ev in out:
        assert "ts" not in ev and "dur" not in ev and not ev.get("wall")
    assert validate_events(out) == []
    # byte-stable: same events -> same lines
    assert [event_to_line(e, True) for e in evs] \
        == [event_to_line(e, True) for e in evs]


def test_validate_events_catches_violations():
    bad = [
        {"kind": "event", "name": "x"},                       # no seq
        {"seq": 1, "kind": "bogus", "name": "x"},             # bad kind
        {"seq": 1, "kind": "event", "name": ""},              # dup seq, no name
        {"seq": 2, "kind": "span", "name": "s", "dur": -1.0,  # negative dur
         "id": "nope"},                                       # non-int id
        {"seq": 3, "kind": "event", "name": "y",
         "attrs": {"a": [1, 2]}},                             # non-scalar attr
    ]
    problems = validate_events(bad)
    for frag in ("seq", "kind", "duplicate", "name", "negative",
                 "id not int", "not a JSON scalar"):
        assert any(frag in p for p in problems), (frag, problems)
    assert validate_events(_sample_events()) == []


def test_chrome_trace_schema():
    evs = _sample_events()
    doc = chrome_trace(evs)
    assert doc["displayTimeUnit"] == "ms"
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert phases.count("X") == 2 and "i" in phases
    assert phases.count("C") == 2
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    jit = next(e for e in spans if e["name"] == "jit")
    assert jit["ts"] == pytest.approx(0.5e6)      # seconds -> us
    assert jit["dur"] == pytest.approx(0.25e6)
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# ProfiledFn + fit_cost_model
# ---------------------------------------------------------------------------

def test_profiled_fn_compile_steady_split():
    import numpy as np

    reg, rec = MetricsRegistry(), FlightRecorder()
    calls = []
    fn = ProfiledFn(lambda x: calls.append(1) or x.sum(), "f",
                    metrics=reg, recorder=rec)
    a8, a16 = np.zeros(8), np.zeros(16)
    fn(a8)                   # compile (new shape)
    fn(a8)                   # steady
    fn(a8)                   # steady
    fn(a16)                  # compile again: retrace on a new shape
    assert fn.n_calls == 4 and fn.n_compiles == 2
    assert fn.compile_s >= 0 and fn.steady_s >= 0
    assert fn.steady_mean_s == pytest.approx(fn.steady_s / 2)
    snap = reg.snapshot()
    by_phase = {r["tags"]["phase"]: r for r in snap
                if r["name"] == "profile/call_s"}
    assert by_phase["compile"]["count"] == 2
    assert by_phase["steady"]["count"] == 2
    assert all(r["wall"] for r in by_phase.values())
    spans = [e for e in rec.events if e["kind"] == "span"]
    assert len(spans) == 4 and all(e.get("wall") for e in spans)
    assert obs.profiled(fn, "f") is fn   # idempotent wrapping


def test_profiled_fn_custom_key():
    fn = ProfiledFn(lambda d: 0, "f", key=lambda a, kw: len(a[0]))
    fn({"a": 1})
    fn({"b": 2})             # same key (len 1) -> steady
    assert fn.n_compiles == 1 and fn.n_calls == 2


def test_fit_cost_model_recovers_line():
    base, slope = 0.010, 0.002
    wave = [(w, base + slope * w) for w in (1, 2, 3, 4, 5)] * 3
    fit = fit_cost_model(wave, admit_obs=[0.02, 0.04])
    assert fit["wave_base_s"] == pytest.approx(base, abs=1e-12)
    assert fit["per_work_s"] == pytest.approx(slope, abs=1e-12)
    assert fit["admit_s"] == pytest.approx(0.03)
    assert fit["n_waves"] == 15 and fit["resid_s"] < 1e-12


def test_fit_cost_model_degenerate():
    # one distinct work level: slope unidentifiable -> pinned to 0
    fit = fit_cost_model([(3, 0.02), (3, 0.04)])
    assert fit["per_work_s"] == 0.0
    assert fit["wave_base_s"] == pytest.approx(0.03)
    empty = fit_cost_model([])
    assert math.isnan(empty["wave_base_s"]) and empty["n_waves"] == 0


# ---------------------------------------------------------------------------
# the compile counter and the profiler-clock spans
# ---------------------------------------------------------------------------

def test_compile_counter_records_each_phase():
    import jax
    import jax.numpy as jnp

    obs.install_compile_counter()
    obs.install_compile_counter()        # idempotent: one listener pair

    def _compile_probe(x):
        return jnp.sin(x) * 2

    f = jax.jit(_compile_probe)
    f(jnp.ones(3))
    f(jnp.ones(5))                       # a new shape compiles again
    recs = obs.compile_records("_compile_probe")
    assert [r["phase"] for r in recs] == ["trace", "lower", "compile"] * 2
    assert all(r["end"] >= r["start"] and r["seconds"] >= 0
               and r["seconds"] == pytest.approx(r["end"] - r["start"])
               for r in recs)
    assert [r["start"] for r in recs] == sorted(r["start"] for r in recs)
    f(jnp.ones(3))                       # cached: jax reports nothing
    f(jnp.ones(5))
    assert obs.compile_records("_compile_probe") == recs
    s = obs.compile_summary("_compile_probe")
    assert s["n_compiles"] == 2 and s["fn"] == "_compile_probe"
    assert s["seconds"] == pytest.approx(
        s["trace_s"] + s["lower_s"] + s["compile_s"])
    assert s["trace_s"] == pytest.approx(
        sum(r["seconds"] for r in recs if r["phase"] == "trace"))
    assert obs.compile_summary("_compile_probe",
                               since=recs[3]["start"])["n_compiles"] == 1


def test_compile_records_are_kept_per_function():
    """Thousands of small eager compiles do not push out the records of
    the train step: each function keeps its latest MAX_PER_FN."""
    import time

    from repro.obs import compiles

    t = time.time()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    compiles._on_span(trace, t, t + 2.0, fun_name="_kept_step")
    n = 5000                             # a bench process makes more
    for i in range(n):
        compiles._on_span(trace, t + 3 + i, t + 3 + i, fun_name="_flood_op")
    assert [r["seconds"] for r in obs.compile_records("_kept_step")] == \
        [2.0]
    flood = obs.compile_records("_flood_op")
    assert len(flood) == compiles.MAX_PER_FN
    assert flood[0]["start"] == t + 3 + n - compiles.MAX_PER_FN
    both = [r["fn"] for r in obs.compile_records()
            if r["fn"] in ("_kept_step", "_flood_op")]
    assert both[0] == "_kept_step" and len(both) == compiles.MAX_PER_FN + 1


def test_compile_records_are_wall_marked_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    obs.install_compile_counter()
    obs.configure()

    def _wall_probe(x):
        return x + 1

    with obs.span("outer"):
        jax.jit(_wall_probe)(jnp.ones(2))
    spans = [e for e in obs.get_recorder().events
             if e["name"].startswith("compile/")
             and e["attrs"].get("fn") == "_wall_probe"]
    assert [e["name"] for e in spans] == ["compile/trace", "compile/lower",
                                          "compile/compile"]
    assert all(e.get("wall") and e["dur"] >= 0 for e in spans)
    outer = next(e for e in obs.get_recorder().events
                 if e["name"] == "outer")
    assert all(e["parent"] == outer["id"] for e in spans)
    assert all(outer["ts"] <= e["ts"] <= outer["ts"] + outer["dur"]
               for e in spans)
    path = tmp_path / "d.jsonl"
    obs.dump(str(path), deterministic=True)
    assert not any(e["name"].startswith("compile/")
                   for e in read_jsonl(str(path)))


def test_span_is_a_profiler_host_event(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    x = jnp.ones(4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("probe/unrecorded", step=1):    # no recorder
            (x * 2).block_until_ready()
        obs.configure()
        with obs.span("probe/recorded", step=2):
            (x * 3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(files[0])
    host = {ev.name: ev for plane in pd.planes
            if plane.name.startswith("/host") for line in plane.lines
            for ev in line.events}
    assert {"probe/unrecorded", "probe/recorded"} <= set(host)
    assert host["probe/unrecorded"].end_ns <= host["probe/recorded"].start_ns
    recorded = [e["name"] for e in obs.get_recorder().events]
    assert recorded == ["probe/recorded"]


def test_train_main_reads_the_device_only_at_log_checkpoint_and_end(
        tmp_path, monkeypatch):
    """The trainer dispatches every step and waits for the device only
    where it reads a value: log steps (with the interval's per-step
    records while tracing), checkpoints and the end of the run."""
    import jax

    from repro.launch import train

    dispatched = []
    setup = train.setup_training

    def counting_setup(*a, **kw):
        state, step, meta = setup(*a, **kw)

        def counted(*args):
            dispatched.append(1)
            return step(*args)
        return state, counted, meta

    def spy(real, log):
        def read(x):
            log.append(len(dispatched))
            return real(x)
        return read

    gets, blocks = [], []
    monkeypatch.setattr(train, "setup_training", counting_setup)
    monkeypatch.setattr(jax, "device_get", spy(jax.device_get, gets))
    monkeypatch.setattr(jax, "block_until_ready",
                        spy(jax.block_until_ready, blocks))
    saves = []
    save = train.save
    monkeypatch.setattr(train, "save", lambda d, k, s: (
        saves.append(len(dispatched)), save(d, k, s)))
    out = tmp_path / "t.jsonl"
    res = train.main(["--arch", "swb2000-blstm", "--reduced", "--learners",
                      "2", "--strategy", "ad_psgd", "--steps", "7",
                      "--log-every", "3", "--batch", "4", "--seq-len", "6",
                      "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every",
                      "5", "--trace-out", str(out)])
    assert len(dispatched) == 7 and saves == [5]
    # dispatches seen at each read: one transfer per log interval (steps
    # 0, 3 and 6), then the end of the run; the checkpoint at step 5
    # reads the state itself
    assert [n for n in gets if n > 0] == [1, 4, 7, 7]
    assert [n for n in blocks if n > 0] == [7]
    assert len(res["losses"]) == 3
    assert res["compiles"]["fn"] == "train_step"
    assert res["compiles"]["n_compiles"] >= 1
    assert res["steady_ms_per_step"] > 0
    evs = [e for e in read_jsonl(str(out))
           if e["kind"] == "event" and e["name"] == "train/step"]
    assert [e["attrs"]["step"] for e in evs] == list(range(7))
    assert all({"loss", "grad_norm", "wire_bytes"} <= set(e["attrs"])
               for e in evs)


def test_train_trace_fills_the_compile_steady_report(tmp_path):
    """A traced training run gives obsreport its compile-vs-steady split
    of ``train_step``; the deterministic export drops both halves."""
    from repro.launch import train
    from repro.launch.obsreport import compile_steady

    argv = ["--arch", "swb2000-blstm", "--reduced", "--learners", "2",
            "--strategy", "ad_psgd", "--steps", "5", "--log-every", "2",
            "--batch", "4", "--seq-len", "6", "--seed", "3"]
    out = tmp_path / "t.jsonl"
    res = train.main(argv + ["--trace-out", str(out)])
    prof = compile_steady(read_jsonl(str(out)))
    assert prof["train_step"]["compile"][0] == res["compiles"]["n_compiles"]
    n, steady_s = prof["train_step"]["steady"]
    assert n == 4
    assert 1e3 * steady_s / n == pytest.approx(res["steady_ms_per_step"])
    det = tmp_path / "d.jsonl"
    train.main(argv + ["--trace-out", str(det), "--trace-deterministic"])
    assert not compile_steady(read_jsonl(str(det)))


# ---------------------------------------------------------------------------
# the module-level sinks
# ---------------------------------------------------------------------------

def test_configure_reset_dispatch(tmp_path):
    assert not obs.enabled()
    obs.event("ignored")                 # no-op, no error
    with obs.span("ignored"):
        pass
    assert obs.dump(str(tmp_path / "x.jsonl")) == 0
    assert not (tmp_path / "x.jsonl").exists()

    obs.configure()
    assert obs.enabled()
    obs.counter("c").inc(2)
    obs.event("e", k=1)
    with obs.span("s"):
        pass
    path, chrome = tmp_path / "t.jsonl", tmp_path / "t_chrome.json"
    n = obs.dump(str(path), chrome=str(chrome))
    evs = read_jsonl(str(path))
    assert n == len(evs) == 3            # event + span + metric snapshot
    assert validate_events(evs) == []
    assert json.load(open(chrome))["traceEvents"]
    obs.reset()
    assert not obs.enabled()
    assert obs.counter("c") is NOOP      # dispatch follows current sink


# ---------------------------------------------------------------------------
# slo.Recorder as a view over the event schema
# ---------------------------------------------------------------------------

def test_recorder_fold_equals_live_table():
    from repro.serving.slo import Recorder, fold_request_events, summarize

    obs.configure()
    live = Recorder()
    live.offered(1, 0, 0.0, deadline=5.0)
    live.offered(2, 1, 0.5)
    live.admitted(1, 0.6)
    live.first_token(1, 0.7)
    live.preempted(1)
    live.admitted(1, 0.9)               # re-admit after preempt: t_admit keeps first
    live.done(1, 1.2, n_tokens=4)
    live.rejected(2, 0.8, reason="pool_full")
    folded = fold_request_events(obs.get_recorder().events)
    assert folded.events == live.events
    assert folded.n_preemptions == live.n_preemptions == 1
    assert summarize(folded) == summarize(live)


def test_recorder_unknown_rid_raises():
    from repro.serving.slo import fold_request_events

    evs = [{"seq": 1, "kind": "event", "name": "request/done",
            "attrs": {"rid": 99, "now": 1.0}}]
    with pytest.raises(KeyError):
        fold_request_events(evs)


def test_slo_csv_shims():
    # moved to repro.obs; slo re-exports stay importable
    from repro.serving.slo import CSV_HEADER, csv_row, print_csv_rows
    assert CSV_HEADER is obs.CSV_HEADER
    assert csv_row is obs.csv_row and print_csv_rows is obs.print_csv_rows
    assert obs.csv_row("a", 1.5, "d") == "a,1.5,d"
    assert obs.csv_row("a", "raw") == "a,raw,"


# ---------------------------------------------------------------------------
# obsreport
# ---------------------------------------------------------------------------

def test_obsreport_span_attribution_and_rows():
    from repro.launch.obsreport import compile_steady, report_rows, \
        span_table

    rec = FlightRecorder(clock=iter(range(100)).__next__)
    with rec.span("outer"):      # entry t=0
        with rec.span("inner"):  # entry t=1, exit t=2 -> dur 1
            pass
    # outer exit t=3 -> dur 3, self 3 - 1 = 2
    rows = {name: (n, tot, slf)
            for name, n, tot, slf in span_table(rec.events)}
    assert rows["inner"] == (1, 1.0, 1.0)
    assert rows["outer"] == (1, 3.0, 2.0)

    rec.add_span("train/step", 0.0, 2.0, wall=True, phase="compile")
    rec.add_span("train/step", 2.0, 0.5, wall=True, phase="steady")
    prof = compile_steady(rec.events)
    assert prof["train/step"]["compile"] == [1, 2.0]
    assert prof["train/step"]["steady"] == [1, 0.5]
    # metric-record fallback when wall spans were stripped
    prof2 = compile_steady([
        {"seq": 1, "kind": "metric", "name": "profile/call_s",
         "tags": {"fn": "f", "phase": "steady"}, "count": 4, "total": 2.0}])
    assert prof2["f"]["steady"] == [4, 2.0]

    names = [r[0] for r in report_rows(rec.events)]
    assert "trace/events" in names and "span/outer" in names
    assert "profile/train/step/compile_s" in names


def test_obsreport_reads_the_counter_and_steady_spans():
    """The trainer's split: the compile counter's ``compile/*`` spans of
    a function whose steady regime is a ``train/steady`` span; other
    functions the counter saw are left out."""
    from repro.launch.obsreport import compile_steady

    rec = FlightRecorder()
    rec.add_span("compile/trace", 3.0, 0.5, wall=True, fn="train_step")
    rec.add_span("compile/lower", 3.5, 0.25, wall=True, fn="train_step")
    rec.add_span("compile/compile", 3.75, 1.25, wall=True, fn="train_step")
    rec.add_span("compile/compile", 5.0, 0.5, wall=True, fn="add")
    rec.add_span("train/steady", 6.0, 3.0, wall=True, fn="train_step",
                 phase="steady", calls=60)
    prof3 = compile_steady(rec.events)
    assert prof3["train_step"]["compile"] == [1, 2.0]
    assert prof3["train_step"]["steady"] == [60, 3.0]
    assert "add" not in prof3


def test_obsreport_cli_rejects_invalid(tmp_path):
    from repro.launch.obsreport import main

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"seq": 1, "kind": "bogus", "name": "x"}\n')
    assert main([str(bad)]) == 1
    good = tmp_path / "good.jsonl"
    write_jsonl(_sample_events(), str(good))
    assert main([str(good), "--csv"]) == 0
    chrome = tmp_path / "c.json"
    assert main([str(good), "--chrome", str(chrome)]) == 0
    assert json.load(open(chrome))["traceEvents"]


# ---------------------------------------------------------------------------
# run-twice bit-equality of the seeded CLIs (the determinism gate)
# ---------------------------------------------------------------------------

def test_train_trace_run_twice_bit_equal(tmp_path):
    traces = []
    for k in (1, 2):
        out = tmp_path / f"t{k}.jsonl"
        r = run(["repro.launch.train", "--arch", "swb2000-blstm",
                 "--reduced", "--learners", "2", "--strategy", "ad_psgd",
                 "--steps", "3", "--log-every", "2",
                 "--trace-out", str(out), "--trace-deterministic"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "timing: compile" in r.stdout and "steady" in r.stdout
        traces.append(out.read_bytes())
        evs = read_jsonl(str(out))
        assert evs and validate_events(evs) == []
        assert any(e["kind"] == "event" and e["name"] == "train/step"
                   for e in evs)
    assert traces[0] == traces[1]


def test_serve_trace_run_twice_bit_equal(tmp_path):
    traces = []
    for k in (1, 2):
        out = tmp_path / f"s{k}.jsonl"
        r = run(["repro.launch.serve", "--arch", "smollm-360m",
                 "--requests", "2", "--slots", "1", "--max-new", "4",
                 "--prompt-len", "8", "--max-len", "32",
                 "--trace-out", str(out), "--trace-deterministic"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "timing: serve/prefill" in r.stdout
        traces.append(out.read_bytes())
        evs = read_jsonl(str(out))
        assert evs and validate_events(evs) == []
        assert any(e["name"].startswith("serve/") for e in evs)
    assert traces[0] == traces[1]
