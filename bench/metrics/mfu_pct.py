"""Model FLOP/s utilization of the whole step: the configuration's model
FLOPs per step (forward + backward, no recomputation) times the steps in
the traced window, over the window, the chips and the chip's bf16 peak."""


def read(ctx):
    steps, window = ctx.trace.steps(), ctx.trace.window_s
    if not steps or window <= 0:
        return None
    return (100.0 * ctx.model_flops_per_step * steps
            / (window * ctx.chips * ctx.peak["bf16_flops"]))
