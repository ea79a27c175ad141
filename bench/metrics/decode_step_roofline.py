"""Share of the bandwidth roofline reached by the full-width member's
decode chunks: the least bytes its steps need (weights once per step, the
live keys and values of active slots) at peak HBM bandwidth, over the
chunks' device time."""


def read(ctx):
    n, t = ctx.main_time("jit__chunk_fn")
    chunks = [c for c in ctx.win.chunks if len(c[0])]
    if not n or not chunks:
        return None
    least = sum(ctx.family.decode_chunk(ctx, *c)[1]
                for c in chunks) / len(chunks)
    return 100.0 * (least / ctx.peaks["hbm_bytes_per_s"]) / (t / n)
