"""The engine on the wall clock, and the benchmark's spans.

``MultiLLMServer`` clocks its stream by decode-chunk index.
:func:`executor_class` gives it, through the per-instance
``_executor_cls`` seam, an executor whose clock is seconds since the window
opened: arrivals are released when they are due, an idle pool sleeps until
the next due time, and after every ``advance`` each live request whose
output has just become non-empty gets its first-token time.

Spans are written from here, around the calls into each layer on the
instances (``jax.profiler.TraceAnnotation``), so that a traced run can say
what the host was doing in each idle gap of the device.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class Window:
    """What one run records on the host clock."""
    seconds: float
    drain_s: float
    main: int                          # full-width endpoint index
    t_open: float = 0.0                # perf_counter at window open
    first: Dict[int, float] = dataclasses.field(default_factory=dict)
    finish: Dict[int, float] = dataclasses.field(default_factory=dict)
    seen: Dict[int, int] = dataclasses.field(default_factory=dict)
    tokens_in_window: int = 0
    windows: List["Routed"] = dataclasses.field(default_factory=list)
    returns: List[float] = dataclasses.field(default_factory=list)
    chunks: List[tuple] = dataclasses.field(default_factory=list)
    prefills: List[int] = dataclasses.field(default_factory=list)
    trace_at: Optional[float] = None   # window seconds to start tracing
    trace_for: float = 0.0
    trace_dir: Optional[str] = None
    tracing: bool = False
    traced: tuple = (0.0, 0.0)         # traced interval, window seconds
    sleep_s: float = 0.0
    route_s: float = 0.0               # host time in route windows
    route_n: int = 0
    route_max: float = 0.0             # the longest one window took
    admit_max: float = 0.0             # the longest one admission took

    def now(self) -> float:
        return time.perf_counter() - self.t_open


@dataclasses.dataclass
class Routed:
    """One routed window as the router saw and answered it: the (padded)
    batch, and the assignment of its valid rows."""
    batch: object
    x: np.ndarray


def executor_class(win: Window):
    """An ``_EngineExecutor`` subclass bound to ``win``."""
    from repro.serving.engine import _EngineExecutor

    class WallClockExecutor(_EngineExecutor):
        def __init__(self, server, max_steps):
            super().__init__(server, max_steps)
            win.t_open = time.perf_counter()

        def now(self) -> float:
            return win.now()

        def advance(self, wake_at):
            now = win.now()
            self._trace_switch(now)
            if now > win.seconds + win.drain_s:
                self.stopped = True
                return [], False
            srv = self.server
            if (wake_at is not None and wake_at > now
                    and not any(e.active_count() for e in srv.endpoints)):
                with jax.profiler.TraceAnnotation("idle_wait"):
                    pause = min(wake_at, win.seconds + win.drain_s) - now
                    time.sleep(max(pause, 0.0))
                win.sleep_s += max(pause, 0.0)
                win.returns.append(win.now())
                return [], True
            if win.tracing:
                ep = srv.endpoints[win.main]
                live = ep.remaining > 0
                win.chunks.append((ep.lens[live].copy(),
                                   ep.remaining[live].copy(), ep.sync_every))
            done, progressed = super().advance(None)
            self._stamp(done)
            win.returns.append(win.now())
            return done, progressed

        def _stamp(self, done):
            t = win.now()
            reqs = list(done)
            for e in self.server.endpoints:
                reqs.extend(e.active_requests())
            for r in reqs:
                n = len(r.output or ())
                grew = n - win.seen.get(r.rid, 0)
                if grew > 0:
                    win.seen[r.rid] = n
                    if t <= win.seconds:
                        win.tokens_in_window += grew
                    win.first.setdefault(r.rid, t)
            for r in done:
                win.finish[r.rid] = t

        def _trace_switch(self, now):
            if win.trace_at is None:
                return
            if not win.tracing and win.traced[1] == 0.0 \
                    and now >= win.trace_at:
                jax.profiler.start_trace(win.trace_dir)
                win.tracing = True
                win.traced = (win.now(), 0.0)
            elif win.tracing and now >= win.trace_at + win.trace_for:
                jax.profiler.stop_trace()
                win.tracing = False
                win.traced = (win.traced[0], now)

    return WallClockExecutor


def annotate(server, router, win: Window):
    """Wrap the calls into each layer on the instances with spans; record
    each routed window (:class:`Routed`), and the full-width member's
    admissions while tracing."""
    for j, ep in enumerate(server.endpoints):
        _wrap(ep, "step_begin", f"step_begin.{j}")
        _wrap(ep, "step_end", f"step_end.{j}")
        admit = ep.admit

        def traced_admit(req, _admit=admit, _j=j):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"admit.{_j}"):
                slot = _admit(req)
            win.admit_max = max(win.admit_max, time.perf_counter() - t0)
            if _j == win.main and win.tracing:
                win.prefills.append(len(req.tokens) - 1)
            return slot
        ep.admit = traced_admit

    route_window = router.route_window

    def traced_route(batch, state, *, n_valid=None, **kw):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("route"):
            x, state = route_window(batch, state, n_valid=n_valid, **kw)
        took = time.perf_counter() - t0
        win.route_s += took
        win.route_n += 1
        win.route_max = max(win.route_max, took)
        n = batch.n if n_valid is None else int(n_valid)
        win.windows.append(Routed(batch, np.asarray(x)[:n]))
        return x, state
    router.route_window = traced_route


def unwrap(endpoints, router):
    """Remove the wrappers :func:`annotate` set on the instances."""
    for ep in endpoints:
        for name in ("step_begin", "step_end", "admit"):
            ep.__dict__.pop(name, None)
    router.__dict__.pop("route_window", None)


def _wrap(obj, name: str, span: str):
    fn = getattr(obj, name)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(span):
            return fn(*a, **kw)
    setattr(obj, name, wrapped)
