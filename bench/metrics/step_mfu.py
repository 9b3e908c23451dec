"""The whole step's share of the chip's bf16 peak: the source graph's
contraction FLOPs per step (forward, plus backward at twice the forward for
a train step; recomputation not counted), times the steps of the traced
window, over the window's length times the peak."""


def read(ctx):
    red, setup = ctx["trace"], ctx["setup"]
    if not ctx["steps"] or not setup.flops_per_step:
        return None
    flops = setup.flops_per_step * ctx["steps"]
    return 100.0 * flops / (red.window_s * ctx["peak"]["bf16_flops_per_s"])
