"""The routed streamfuse conv kernels' share of their roofline: the least
time of the routed pad-conv-relu chains' work over their summed device time
in the trace."""

import work


def read(ctx):
    return work.roofline_share("streamfuse.conv", ctx["setup"].routes,
                               ctx["calls"], ctx["trace"].ops[0], ctx["peak"])
