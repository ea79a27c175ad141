"""Model FLOPs of the tokens the full-width member decoded, over its
decode chunks' device time at the chip's bf16 peak."""


def read(ctx):
    n, t = ctx.main_time("jit__chunk_fn")
    chunks = [c for c in ctx.win.chunks if len(c[0])]
    if not n or not chunks:
        return None
    work = sum(ctx.family.decode_chunk(ctx, *c)[0]
               for c in chunks) / len(chunks)
    return 100.0 * work / ((t / n) * ctx.peaks["bf16_flops"])
