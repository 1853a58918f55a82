"""The set-up compile of the program's train step, read from the
program's own compile counter (``repro.obs.compile_records``) in this
process.

The counter keeps one record per phase (``trace``, ``lower``,
``compile``) each time jax reports one for a function named
``train_step``.  The set-up group is the step's latest trace of at least
:data:`MIN_TRACE_S` that a lowering and a compile (or persistent-cache
load) follow, with the first of each after it.  A fresh trace of the
step takes seconds (2.5 s on a v5e host at the cell's size, 0.6-2.2 s
for the tiny CPU copy); jax answers a repeated one (a call that missed
the dispatch fast path, or the harness's lowering after a traced
window) from its cache in about 0.1 ms, and lowers nothing.  A process
that runs several cells traces each cell's new step in full.  A program
without the counter gives no records, and the readers nothing.
"""
from __future__ import annotations

STEP = "train_step"
MIN_TRACE_S = 0.1


def step_records():
    """The compile counter's records of the train step, or None when the
    program has no compile counter."""
    try:
        from repro.obs import compile_records
    except ImportError:
        return None
    return compile_records(STEP)


def setup_group(records):
    """(trace, lower, compile) records of the step's set-up compile, or
    None."""
    records = records or []
    for i in range(len(records) - 1, -1, -1):
        r = records[i]
        if r["phase"] != "trace" or r["seconds"] < MIN_TRACE_S:
            continue
        after = records[i + 1:]
        lower = next((x for x in after if x["phase"] == "lower"), None)
        comp = next((x for x in after if x["phase"] == "compile"), None)
        if lower is not None and comp is not None:
            return r, lower, comp
    return None
