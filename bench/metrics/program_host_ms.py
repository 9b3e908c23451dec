"""Mean host milliseconds per step inside the program's own ``codo.*``
spans, less those in ``codo.sync`` (where the host waits for the device):
the program's host path, from the aligned trace (``spans.py``) in
``ctx["program"]``."""


def read(ctx):
    aligned = ctx.get("program")
    if aligned is None or not ctx["steps"]:
        return None
    return aligned.program_host_s() / ctx["steps"] * 1e3
