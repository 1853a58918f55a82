"""The compile counter: when jax traced, lowered and compiled each jitted
function, from jax's own monitoring events (docs/observability.md).

jax reports each compile phase of a jitted function through
``jax.monitoring`` as a time span with the function's name:

* ``/jax/core/compile/jaxpr_trace_duration`` — tracing to a jaxpr
  (phase ``trace``);
* ``/jax/core/compile/jaxpr_to_mlir_module_duration`` — lowering the
  jaxpr to an MLIR module (phase ``lower``);
* ``/jax/core/compile/backend_compile_duration`` — the backend compile,
  or the load of the executable when the persistent compilation cache
  answers (phase ``compile``).  A load also reports
  ``/jax/compilation_cache/cache_retrieval_time_sec``; the compile
  record it belongs to carries ``cache_hit=True``.

:func:`install` registers one listener for each kind once per process
(idempotent; jax is imported only then, so ``repro.obs`` imports
without it).  The listeners run only when jax compiles, so the counter
costs nothing between compiles.  Records are dicts ``{"fn", "phase",
"seconds", "start", "end", ...}`` with ``start``/``end`` on
``time.time()``, jax's clock for these events; ``fn`` is the jitted
function's name (``jit(f)`` reads ``f``).  Each function keeps its
latest :data:`MAX_PER_FN` records: a process that compiles thousands of
small eager operations (``add``, ``multiply``, ...) does not push out
the records of its train step.  While observability is configured each record also
enters the flight recorder as a wall-marked span ``compile/<phase>``,
which the deterministic export drops.
"""
from __future__ import annotations

import threading
import time
from collections import deque

PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
MAX_PER_FN = 64

_records: dict = {}         # fn -> deque of its latest records
_lock = threading.Lock()
_installed = False
_loads: dict = {}           # thread id -> seconds of its last cache load


def fn_name(name: str) -> str:
    """The function's own name: jax names a lowered module ``jit(f)``."""
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _on_span(event: str, start: float, end: float, **kw) -> None:
    phase = PHASES.get(event)
    if phase is None:
        return
    rec = {"fn": fn_name(str(kw.get("fun_name", ""))), "phase": phase,
           "seconds": end - start, "start": start, "end": end}
    if phase == "compile":
        load = _loads.pop(threading.get_ident(), None)
        rec["cache_hit"] = load is not None
    with _lock:
        _records.setdefault(rec["fn"], deque(maxlen=MAX_PER_FN)).append(rec)
    from repro import obs           # the sinks current at this moment
    if obs.enabled():
        ts = time.perf_counter() - (time.time() - start)
        obs.add_span(f"compile/{phase}", ts, end - start, wall=True,
                     fn=rec["fn"])


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == CACHE_RETRIEVAL:
        _loads[threading.get_ident()] = seconds


def install() -> None:
    """Register the listener pair with ``jax.monitoring`` (once)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_duration_secs_listener(_on_duration)


def records(fn: str = None) -> list:
    """Copies of the kept records in the order they began; only
    ``fn``'s if given."""
    with _lock:
        kept = ([r for q in _records.values() for r in q] if fn is None
                else list(_records.get(fn, ())))
    return [dict(r) for r in sorted(kept, key=lambda r: r["start"])]


def summary(fn: str, since: float = 0.0) -> dict:
    """Compiles of ``fn`` that began at or after ``since`` (time.time()):
    their count, seconds per phase, and the persistent-cache loads."""
    recs = [r for r in records(fn) if r["start"] >= since]
    out = {"fn": fn, "n_compiles": 0, "cache_hits": 0}
    for phase in PHASES.values():
        out[f"{phase}_s"] = sum(r["seconds"] for r in recs
                                if r["phase"] == phase)
    for r in recs:
        if r["phase"] == "compile":
            out["n_compiles"] += 1
            out["cache_hits"] += bool(r.get("cache_hit"))
    out["seconds"] = sum(out[f"{p}_s"] for p in PHASES.values())
    return out
