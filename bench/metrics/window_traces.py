"""Times JAX traced a jitted program inside the traced window (a compile or
a persistent-cache load): the lowering's ``traces`` counter
(``LOWER_CACHE_STATS``), summed over programs, counted around the window
into ``ctx["counters"]``."""


def read(ctx):
    return (ctx.get("counters") or {}).get("traces")
