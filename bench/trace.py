"""Reduction of a profiler trace to device events, busy time and host spans.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two flat lists: device events (one per XLA module execution, from each
``/device:TPU:n`` plane's "XLA Modules" line, plus its op events) and the
benchmark's host spans.  Everything after ``load`` works on those lists, so
``bench/tests`` checks it on a small recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Dict, List, Sequence, Tuple

# host spans written by bench/clock.py
SPAN_PREFIXES = ("route", "admit.", "step_begin.", "step_end.", "idle_wait")


@dataclasses.dataclass
class Ev:
    name: str
    start: float        # seconds, profiler clock
    dur: float          # seconds
    module: str = ""
    program: int = -1
    kind: str = "module"   # module | op | span
    device: int = 0


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> List[Ev]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Ev] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                kind = {"XLA Modules": "module", "XLA Ops": "op"}.get(
                    line.name)
                if kind is None:
                    continue
                for e in line.events:
                    st = dict(e.stats)
                    out.append(Ev(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9,
                                  str(st.get("hlo_module", "")),
                                  _program(e.name, st), kind, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        out.append(Ev(e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9, kind="span"))
    return out


def _program(name: str, stats: dict) -> int:
    """The executable's id: the ``program_id`` stat, else the fingerprint a
    TPU module event carries in its name, ``jit_prefill(<id>)``."""
    if "program_id" in stats:
        return int(stats["program_id"])
    if name.endswith(")") and "(" in name:
        tail = name.rsplit("(", 1)[1][:-1]
        if tail.isdigit():
            return int(tail)
    return -1


def save(events: Sequence[Ev], path: str):
    with open(path, "w") as f:
        json.dump([dataclasses.asdict(e) for e in events], f)


def read(path: str) -> List[Ev]:
    with open(path) as f:
        return [Ev(**d) for d in json.load(f)]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    """Merged (start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def device_busy(events: Sequence[Ev], lo: float, hi: float,
                device: int = 0) -> float:
    """Seconds of ``[lo, hi]`` in which a module ran on ``device``."""
    iv = [(max(e.start, lo), min(e.start + e.dur, hi)) for e in events
          if e.kind == "module" and e.device == device]
    return sum(max(0.0, e - s) for s, e in union(iv))


def devices(events: Sequence[Ev]) -> List[int]:
    return sorted({e.device for e in events if e.kind == "module"})


def programs(events: Sequence[Ev], module: str) -> Dict[int, Tuple[int,
                                                                   float]]:
    """program id -> (calls, device seconds) of one module name."""
    out: Dict[int, Tuple[int, float]] = {}
    for e in events:
        if e.kind == "module" and _module_name(e) == module:
            n, t = out.get(e.program, (0, 0.0))
            out[e.program] = (n + 1, t + e.dur)
    return out


def _module_name(e: Ev) -> str:
    """The jitted function's module name: ``jit_prefill`` from an event
    named ``jit_prefill(123)`` or carrying ``hlo_module``."""
    return e.module or e.name.split("(", 1)[0]


def window(events: Sequence[Ev]) -> Tuple[float, float]:
    """The traced interval: from the first to the last event of any kind."""
    ts = [e.start for e in events] + [e.start + e.dur for e in events]
    return min(ts), max(ts)


def top_modules(events: Sequence[Ev], k: int = 10) -> List[list]:
    tot: Dict[str, float] = {}
    for e in events:
        if e.kind == "module":
            key = f"{_module_name(e)}#{e.program}"
            tot[key] = tot.get(key, 0.0) + e.dur
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(events: Sequence[Ev], lo: float, hi: float, k: int = 10,
              device: int = 0) -> List[list]:
    """The ``k`` longest device-idle gaps in ``[lo, hi]``, each named by the
    innermost host span covering its midpoint (``host`` if none)."""
    busy = union([(e.start, e.start + e.dur) for e in events
                  if e.kind == "module" and e.device == device])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    spans = [e for e in events if e.kind == "span"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp.start <= mid <= sp.start + sp.dur]
        name = min(cover, key=lambda sp: sp.dur).name if cover else "host"
        out.append([name, e - s])
    return out


def main_programs(progs: Dict[int, Tuple[int, float]],
                  floor_s: float) -> List[int]:
    """Program ids of the full-width member: those whose mean device time
    per call is at least ``floor_s``, the time its weights take to read at
    peak bandwidth (once per step).  No program of that member can run
    faster; a stand-in's programs, at smoke width, run far below it."""
    return [p for p, (n, t) in progs.items() if n and t / n >= floor_s]
