"""Jitted program calls per step over the traced window: the lowering's
``dispatches`` counter (``LOWER_CACHE_STATS``), summed over programs,
counted around the window into ``ctx["counters"]``."""


def read(ctx):
    counted = ctx.get("counters") or {}
    if "dispatches" not in counted or not ctx["steps"]:
        return None
    return counted["dispatches"] / ctx["steps"]
