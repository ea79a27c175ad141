"""Median milliseconds from admission to the first output token on the
host (the program's ``request.first`` records), over the requests released
between 10% of the window and the start of the trace."""
import numpy as np


def read(ctx):
    try:
        from repro.common import tracing
    except ImportError:                 # a program without the recorder
        return None
    w = ctx.win
    lo, hi = w.t_open + 0.1 * w.seconds, w.t_open + w.traced[0]
    # a request's two records meet at its admission stamp
    admitted = {(rid, end) for _, name, start, end, _, rid in tracing.spans
                if name == "request.queue" and lo <= start < hi}
    d = [end - start for _, name, start, end, _, rid in tracing.spans
         if name == "request.first" and (rid, start) in admitted]
    return 1e3 * float(np.median(d)) if d else None
