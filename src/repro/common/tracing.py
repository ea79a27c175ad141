"""In-program spans, request stages and counts for the serving path.

One recorder, at module level, in the idiom of the sanitize and fault
planes' ``counters``:

- :func:`span` times a block of host code where the work happens (one loop
  iteration, one route window, one admission, one endpoint chunk).  It
  stamps start and end with ``time.perf_counter()``, notes the enclosing
  span and the request id, and opens a ``jax.profiler.TraceAnnotation`` of
  the same name, so a profiler trace carries the span on its own clock.
  The two clocks differ by one offset per trace.
- :func:`record` adds an interval whose ends were stamped elsewhere: the
  engine writes each served request's stages (``request.queue``: released →
  admitted, ``request.first``: admitted → first token) when it completes.
- :func:`count` notes that a count its owner keeps grew by ``n`` (an
  endpoint's tokens emitted in one chunk), so a reader can sum the growth
  over a range of time.

Records are plain tuples, in two rings of ``RING`` each:

- ``spans``: ``(sid, name, start, end, parent sid, rid)``;
- ``samples``: ``(name, label, t, n)``.

Plain tuples of numbers, strings and None leave the garbage collector's
tracking at the first collection they survive, so a full ring adds nothing
to later collections.  A 45 s window of the benchmark's cells (~150 loop
iterations of ~290 ms, 22–30 requests a second) fills 8–11k span records
and ~2k samples; a ring holds several such windows.  There is no switch:
with no profiler running a span costs two clock reads, one append and a
no-op ``TraceMe``.  Nothing is recorded per slot or per token.
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import Dict, Hashable, Optional

from jax.profiler import TraceAnnotation

RING = 1 << 16

spans: collections.deque = collections.deque(maxlen=RING)
samples: collections.deque = collections.deque(maxlen=RING)
#: seconds spent in spans of each name, over the process: a caller takes
#: the difference around the work it times (route and fold overhead)
seconds: Dict[str, float] = {}

_ids = itertools.count()
_open: list = []          # sids of the spans open now, innermost last


class span:
    """``with span("chunk.fetch.3"): ...`` — one record per block."""

    __slots__ = ("name", "rid", "sid", "parent", "start", "_ann")

    def __init__(self, name: str, rid: Optional[int] = None):
        self.name = name
        self.rid = rid

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.parent = _open[-1] if _open else None
        self.sid = next(_ids)
        _open.append(self.sid)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        _open.pop()
        spans.append((self.sid, self.name, self.start, end, self.parent,
                      self.rid))
        seconds[self.name] = seconds.get(self.name, 0.0) + (end - self.start)
        self._ann.__exit__(*exc)
        return False


def record(name: str, start: float, end: float, rid: Optional[int] = None):
    """Add an interval whose ends were stamped elsewhere (no parent)."""
    spans.append((next(_ids), name, start, end, None, rid))


def count(name: str, label: Hashable, n: int):
    """Note that the count ``name`` of ``label`` grew by ``n`` now."""
    samples.append((name, label, time.perf_counter(), n))


def total(*names: str) -> float:
    """Seconds spent so far in spans of these names."""
    return sum(seconds.get(n, 0.0) for n in names)


def reset():
    """Forget every record (tests start from an empty recorder)."""
    spans.clear()
    samples.clear()
    seconds.clear()
