"""Seconds the six dataflow passes took, summed over every
``codo.compile`` of the run: ``PassRecord.seconds``, the program's own
host-clock span around each pass."""


def read(ctx):
    return ctx["setup"].passes_s
