"""Host seconds from ``lower()`` through the first finished call: routing,
building the jitted program, XLA/Mosaic compile (served from JAX's
persistent cache after a checkout's first run); for the served cell, the
warm-up of every batch size."""


def read(ctx):
    return ctx["setup"].lower_compile_s
