"""Share of the traced window in which no op runs on a chip (1 - the
union of op intervals over the window), averaged over the chips."""
from harness import trace


def read(ctx):
    tr = ctx.trace
    if not tr.ops or tr.window_s <= 0:
        return None
    span = tr.window[1] - tr.window[0]
    idle = [1.0 - trace.busy_ns(evs, tr.window) / span
            for evs in tr.ops.values()]
    return 100.0 * sum(idle) / len(idle)
