"""Median milliseconds of one pass of the serving loop (the program's
``loop.iter`` spans), over the passes that lie wholly between 10% of the
window and the start of the trace, while the profiler is off."""
import numpy as np


def read(ctx):
    try:
        from repro.common import tracing
    except ImportError:                 # a program without the recorder
        return None
    w = ctx.win
    lo, hi = w.t_open + 0.1 * w.seconds, w.t_open + w.traced[0]
    d = [end - start for _, name, start, end, _, _ in tracing.spans
         if name == "loop.iter" and lo <= start and end <= hi]
    return 1e3 * float(np.median(d)) if d else None
