"""The flash-attention kernel's share of its roofline: the least time of
its chain's work (``work.chain_work``: q@kT and p@v FLOPs, q, kT, v in and
the output out) over its summed device time in the trace."""

import work


def read(ctx):
    return work.roofline_share("flashattn", ctx["setup"].routes,
                               ctx["calls"], ctx["trace"].ops[0], ctx["peak"])
