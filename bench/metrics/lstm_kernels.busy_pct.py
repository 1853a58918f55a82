"""Share of the chips' busy time taken by the LSTM Mosaic kernels."""
from harness import kernels, trace


def read(ctx):
    names = {n for n, call in ctx.mosaic.items()
             if kernels.lstm_cost(call) is not None}
    tr = ctx.trace
    if not trace.matched(tr, names, "LSTM kernels"):
        return None
    mine = busy = 0.0
    for evs in tr.ops.values():
        mine += trace.length(trace.clip(
            [(s, e) for n, s, e in evs if n in names], tr.window))
        busy += trace.busy_ns(evs, tr.window)
    return 100.0 * mine / busy
