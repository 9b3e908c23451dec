"""Requests per dispatch in the traced window, from the runtime's
``ServeStats`` counters: (batched + per-request executions) / batches."""


def read(ctx):
    if not ctx.get("batches"):
        return None
    return ctx["requests"] / ctx["batches"]
