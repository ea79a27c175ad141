"""Device milliseconds per decode chunk of the full-width member, over
the traced interval."""


def read(ctx):
    n, t = ctx.main_time("jit__chunk_fn")
    return 1e3 * t / n if n else None
