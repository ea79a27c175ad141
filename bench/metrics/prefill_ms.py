"""Device milliseconds per prefill of the full-width member, over the
traced interval."""


def read(ctx):
    n, t = ctx.main_time("jit_prefill")
    return 1e3 * t / n if n else None
