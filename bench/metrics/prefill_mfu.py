"""Model FLOPs of the full-width member's prefills (real prompt tokens),
over their device time at the chip's bf16 peak."""


def read(ctx):
    n, t = ctx.main_time("jit_prefill")
    if not n or not ctx.win.prefills:
        return None
    work = sum(ctx.family.prefill(ctx, p) for p in ctx.win.prefills)
    work *= n / len(ctx.win.prefills)
    return 100.0 * work / (t * ctx.peaks["bf16_flops"])
