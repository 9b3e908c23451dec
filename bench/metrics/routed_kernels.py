"""Routes to Pallas kernels in the lowered programs the window runs,
counted from the program's routing record."""


def read(ctx):
    return len(ctx["setup"].routes)
