"""Share of the block table the full-width member's decode attention still
reads: the KV pages its chunks' occupied slots attend to over the pages of
every slot's whole block-table row (the program's ``kv_pages`` and
``kv_pages_table`` counts, recorded as each chunk's results reach the host),
over its chunks between 10% of the window and the start of the trace."""


def read(ctx):
    try:
        from repro.common import tracing
    except ImportError:                 # a program without the recorder
        return None
    w = ctx.win
    lo, hi = w.t_open + 0.1 * w.seconds, w.t_open + w.traced[0]
    grew = {"kv_pages": 0, "kv_pages_table": 0}
    for name, label, t, n in tracing.samples:
        if label == w.main and name in grew and lo <= t < hi:
            grew[name] += n
    table = grew["kv_pages_table"]
    return 100.0 * grew["kv_pages"] / table if table else None
