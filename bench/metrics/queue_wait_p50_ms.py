"""Median milliseconds from release into the ready queue to admission
(the program's ``request.queue`` records: route windows, waits for a free
slot, requeues), over the requests released between 10% of the window and
the start of the trace."""
import numpy as np


def read(ctx):
    try:
        from repro.common import tracing
    except ImportError:                 # a program without the recorder
        return None
    w = ctx.win
    lo, hi = w.t_open + 0.1 * w.seconds, w.t_open + w.traced[0]
    d = [end - start for _, name, start, end, _, _ in tracing.spans
         if name == "request.queue" and lo <= start < hi]
    return 1e3 * float(np.median(d)) if d else None
