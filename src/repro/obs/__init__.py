"""repro.obs — the unified observability layer (docs/observability.md).

One process-wide pair of sinks that every surface emits through:

* a :class:`~repro.obs.metrics.MetricsRegistry` of tagged counters /
  gauges / histograms (per-step training scalars, serving service
  times, kernel VMEM accounting, bytes on wire), and
* a :class:`~repro.obs.trace.FlightRecorder` — a bounded ring of
  schema events (spans, instants, metric snapshots) exportable as
  JSONL and as Chrome ``trace_event`` JSON.

Two instruments are always on, whether or not the sinks are:

* :func:`span` opens a ``jax.profiler.TraceAnnotation`` of its name, so
  the program's spans sit on the profiler's clock beside the device
  ops of any ``jax.profiler`` trace (a no-op while no profiler session
  runs); it records into the flight recorder only when configured;
* the compile counter (:mod:`repro.obs.compiles`, installed by
  :func:`install_compile_counter`) keeps a record of every trace,
  lowering and backend compile jax reports, behind
  :func:`compile_records` / :func:`compile_summary`; its listeners run
  only when jax compiles.

The default is the **no-op pair**: until :func:`configure` is called
(the launchers call it when ``--trace-out`` is passed) every
instrument is a shared do-nothing object and a span only a profiler
annotation, so uninstrumented runs pay one method call per site and
stay bit-identical — the
property the recovery / transport-golden / paged≡dense exactness
tests rely on.

Module-level helpers (:func:`event`, :func:`span`, :func:`metric_*`)
always dispatch through the *current* sinks, so call sites never cache
a stale registry across :func:`configure`/:func:`reset`.

This module is also the single source of the ``name,value,derived``
stats CSV schema (:func:`csv_row` / :func:`print_csv_rows`), formerly
in ``repro.serving.slo`` (which keeps deprecation shims).
"""
from __future__ import annotations

import json
from contextlib import contextmanager

from .metrics import (  # noqa: F401  (re-exports)
    MAX_SAMPLES,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NOOP,
    NULL_METRICS,
    NullRegistry,
    nearest_rank,
)
from .trace import (  # noqa: F401
    DEFAULT_MAXLEN,
    FlightRecorder,
    KINDS,
    NULL_RECORDER,
    NullRecorder,
    chrome_trace,
    read_jsonl,
    validate_events,
    write_jsonl,
)
from .profile import (  # noqa: F401
    ProfiledFn,
    fit_cost_model,
    profiled,
)
from .compiles import (  # noqa: F401
    install as install_compile_counter,
    records as compile_records,
    summary as compile_summary,
)

# ---------------------------------------------------------------------------
# process-global sinks (no-op until configure())
# ---------------------------------------------------------------------------

_metrics: MetricsRegistry = NULL_METRICS
_recorder: FlightRecorder = NULL_RECORDER


def configure(maxlen: int = DEFAULT_MAXLEN):
    """Turn observability on: install a live registry + recorder pair
    (replacing the no-op defaults) and return ``(metrics, recorder)``."""
    global _metrics, _recorder
    _metrics = MetricsRegistry()
    _recorder = FlightRecorder(maxlen=maxlen)
    return _metrics, _recorder


def reset() -> None:
    """Back to the zero-overhead no-op defaults (tests; end of a run)."""
    global _metrics, _recorder
    _metrics = NULL_METRICS
    _recorder = NULL_RECORDER


def enabled() -> bool:
    return _metrics is not NULL_METRICS


def get_metrics() -> MetricsRegistry:
    return _metrics


def get_recorder() -> FlightRecorder:
    return _recorder


# thin always-current dispatchers (never cache the sink at a call site)

def event(name: str, **attrs) -> None:
    _recorder.event(name, **attrs)


_annotation = None          # jax.profiler.TraceAnnotation, found lazily


def _annotate(name: str):
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:         # obs imports and runs without jax
            from contextlib import nullcontext as TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(name)


@contextmanager
def _recorded(name: str, attrs: dict):
    with _annotate(name), _recorder.span(name, **attrs):
        yield


def span(name: str, **attrs):
    """Timed region: a profiler annotation of ``name`` always, and a
    flight-recorder span with ``attrs`` while observability is
    configured."""
    if _recorder is NULL_RECORDER:
        return _annotate(name)
    return _recorded(name, attrs)


def add_span(name: str, t0: float, dur: float, **attrs) -> None:
    _recorder.add_span(name, t0, dur, **attrs)


def counter(name: str, **tags):
    return _metrics.counter(name, **tags)


def gauge(name: str, **tags):
    return _metrics.gauge(name, **tags)


def histogram(name: str, wall: bool = False, **tags):
    return _metrics.histogram(name, wall=wall, **tags)


def flush_metrics() -> int:
    """Append the registry snapshot to the flight recorder as
    ``metric`` events (deterministic order); returns records written."""
    recs = _metrics.snapshot()
    for rec in recs:
        _recorder.metric(rec)
    return len(recs)


def dump(path: str, deterministic: bool = False,
         chrome: str = None) -> int:
    """Flush the metrics snapshot and write the recorder to ``path`` as
    JSONL (optionally also ``chrome`` as trace_event JSON); returns
    JSONL lines written.  No-op (returns 0) while disabled."""
    if not enabled():
        return 0
    flush_metrics()
    events_ = _recorder.events
    n = write_jsonl(events_, path, deterministic=deterministic)
    if chrome:
        with open(chrome, "w", encoding="utf-8") as f:
            json.dump(chrome_trace(events_), f)
    return n


# ---------------------------------------------------------------------------
# the shared ``name,value,derived`` stats CSV schema
# (moved here from repro.serving.slo — single formatting source)
# ---------------------------------------------------------------------------

CSV_HEADER = "name,value,derived"


def csv_row(name, value, derived="") -> str:
    """One row of the shared stats schema (evaluate/load)."""
    try:
        value = f"{float(value):.6g}"
    except (TypeError, ValueError):
        value = str(value)
    return f"{name},{value},{derived}"


def print_csv_rows(rows, header: bool = False) -> None:
    """Print ``(name, value, derived)`` rows in the shared schema."""
    if header:
        print(CSV_HEADER)
    for name, value, derived in rows:
        print(csv_row(name, value, derived), flush=True)
