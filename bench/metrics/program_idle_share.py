"""Share of the traced window in which the device idles while the host is
inside a ``codo.*`` span other than ``codo.sync``: idle time that the
program's own host path holds, on the device trace's clock aligned to the
host's (``spans.py``, ``ctx["program"]``)."""


def read(ctx):
    aligned = ctx.get("program")
    return None if aligned is None else aligned.program_idle_share()
