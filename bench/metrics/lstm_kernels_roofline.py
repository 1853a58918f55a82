"""Roofline share of the LSTM Mosaic kernels: the least time the chip
could take for their work (each kernel's FLOPs and bytes from its
operand shapes, ``harness.kernels.lstm_cost``, against the peaks table)
over their summed device time in the traced window."""
from harness import kernels, trace


def read(ctx):
    cost = {}
    for name, call in ctx.mosaic.items():
        c = kernels.lstm_cost(call)
        if c is not None:
            cost[name] = kernels.min_time_s(*c, ctx.peak)[0]
    if not trace.matched(ctx.trace, set(cost), "LSTM kernels"):
        return None
    least = spent = 0.0
    for evs in ctx.trace.ops.values():
        for n, s, e in trace.clip_events(evs, ctx.trace.window):
            if n in cost:
                least += cost[n]
                spent += (e - s) * 1e-9
    return 100.0 * least / spent
