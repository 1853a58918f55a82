"""From the profiler's trace to intervals, and the interval arithmetic the
per-layer metrics share.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the
device planes' op lines (one op interval list per chip) and the
benchmark's own host spans (``bench/...`` annotations), on one clock.
The traced window is the ``bench/window`` span: from the first step's
fetch until every step sent has finished.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

SPAN_PREFIX = "bench/"


@dataclass
class Trace:
    ops: dict                       # device -> [(name, start_ns, end_ns)]
    spans: list                     # [(name, start_ns, end_ns)] host
    window: tuple = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def steps(self) -> int:
        return sum(1 for n, _, _ in self.spans if n == SPAN_PREFIX + "step")


def _device_index(plane_name: str):
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def _op_line(plane):
    """The line of a device plane that holds one event per executed op."""
    lines = {ln.name: ln for ln in plane.lines}
    if "XLA Ops" in lines:
        return lines["XLA Ops"]
    ops = [n for n in lines if n.endswith(" Ops") and "Framework" not in n]
    return lines[ops[0]] if len(ops) == 1 else None


_HLO_NAME = re.compile(r"^%([^\s=]+)\s*=")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event.  On the TPU the
    event carries the instruction's whole text (``%fusion.7 = bf16[...]
    fusion(...)``); its name is what stands before the ``=``."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def from_events(ops: dict, spans: list) -> Trace:
    """The traced window is the ``bench/window`` span where there is one
    (the steps and the wait for the last of them), else the steps'."""
    win = [(s, e) for n, s, e in spans if n == SPAN_PREFIX + "window"]
    steps = win or [(s, e) for n, s, e in spans
                    if n == SPAN_PREFIX + "step"]
    window = ((min(s for s, _ in steps), max(e for _, e in steps))
              if steps else (0.0, 0.0))
    return Trace(ops=ops, spans=spans, window=window)


def load(trace_dir: str) -> Trace:
    import jax

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    ops, spans = {}, []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            line = _op_line(plane)
            if line is None:
                continue
            evs = ops.setdefault(dev, [])
            evs += [(op_name(ev.name), ev.start_ns, ev.end_ns)
                    for ev in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return from_events(ops, sorted(spans, key=lambda x: x[1]))


def clip(intervals, window):
    lo, hi = window
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def merge(intervals):
    """Union of intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def busy_ns(events, window) -> float:
    return length(clip([(s, e) for _, s, e in events], window))


def idle_gaps(events, window):
    """Disjoint (start, end) stretches of the window with no op."""
    busy = merge(clip([(s, e) for _, s, e in events], window))
    gaps, t = [], window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def matched(tr: Trace, names: set, what: str) -> bool:
    """Whether the compiled step has any of ``what`` (``names``, its
    instruction names) in a trace with device ops; raises when it has but
    the trace's op events carry none of those names, so a reader never
    reads silence as absence."""
    if not names or not tr.ops:
        return False
    if not any(n in names for evs in tr.ops.values() for n, _, _ in evs):
        raise LookupError(
            f"the compiled step has {len(names)} {what} "
            f"({sorted(names)[:3]}...) but no op of the trace carries one "
            f"of their names")
    return True


def span_over(spans, lo: float, hi: float) -> str:
    """The host span (other than ``bench/window`` and ``bench/step``)
    that covers most of the stretch [lo, hi)."""
    best, most = "none", 0.0
    outer = {SPAN_PREFIX + "window", SPAN_PREFIX + "step"}
    for n, s, e in spans:
        cover = min(e, hi) - max(s, lo)
        if n not in outer and cover > most:
            best, most = n[len(SPAN_PREFIX):], cover
    return best


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device ops that took most time (summed over chips, averaged
    per chip) and the longest idle gaps by the host span open in them."""
    n_dev = max(len(tr.ops), 1)
    by_op = {}
    for evs in tr.ops.values():
        for n, s, e in clip_events(evs, tr.window):
            by_op[n] = by_op.get(n, 0.0) + (e - s)
    dev_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    first = min(tr.ops) if tr.ops else None
    gaps = idle_gaps(tr.ops.get(first, []), tr.window) if tr.ops else []
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, v * 1e-9 / n_dev] for n, v in dev_ops],
        "idle_gaps": [[span_over(tr.spans, s, e), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def clip_events(events, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]
