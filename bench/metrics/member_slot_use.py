"""Share of the full-width member's slot-steps that emitted a token: the
tokens its chunks emitted over the slots times steps they computed (the
program's ``tokens`` and ``slot_steps`` counts, recorded as each chunk's
results reach the host), over its chunks between 10% of the window and the
start of the trace."""


def read(ctx):
    try:
        from repro.common import tracing
    except ImportError:                 # a program without the recorder
        return None
    w = ctx.win
    lo, hi = w.t_open + 0.1 * w.seconds, w.t_open + w.traced[0]
    grew = {"tokens": 0, "slot_steps": 0}
    for name, label, t, n in tracing.samples:
        if label == w.main and name in grew and lo <= t < hi:
            grew[name] += n
    steps = grew["slot_steps"]
    return 100.0 * grew["tokens"] / steps if steps else None
