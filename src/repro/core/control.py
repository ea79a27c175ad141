"""Streaming dual control plane (ISSUE 5): the ONE admission / dispatch /
completion loop shared by the event-driven simulator
(``repro.core.scheduler.run_serving``) and the real serving engine
(``repro.serving.engine.MultiLLMServer``).

Before this module, both drivers carried their own copy of the paper's
§4.2 capacity rule (``batch_size or cap_total // 2`` / ``max_inflight``),
their own admission-then-advance loop, and their own fold-back buffering —
and both released every query at t=0.  Now:

- :class:`AdmissionRule` is the single home of the capacity rule.
- :class:`StreamController` owns the routing side of the stream: it carries
  the :class:`~repro.core.optimizer.DualState` across windows (warm-started
  multipliers + the cumulative budget/α ledger), computes each window's
  share of the remaining horizon, and threads the state through
  ``Policy.route_window``.  With ``stream=False`` it degrades to the
  stateless one-shot ``Policy.route`` (the pre-streaming behavior).
- :class:`FoldBuffer` is the shared buffered fold-back of completions into
  the policy's predictor store.
- :class:`ControlLoop` drives an *executor* (the simulator's event queue or
  the engine's endpoint pool) through release-arrivals → admit-window →
  advance, so "streaming" means queries arriving over time with the live
  fleet state feeding the workload constraint — not ``batch_size=1``.

The executor duck-type:

    now() -> float                     stream clock (sim seconds / steps)
    loads() / counts() -> (M,) arrays  per-model capacity and in-flight
    dispatch(items, x) -> rejected     execute one routed window; return the
                                       items that found no capacity
    advance(wake_at) -> (done, bool)   move the clock one event/step; return
                                       completed items + progress flag.
                                       ``wake_at`` is the next time anything
                                       new can happen (arrival / window
                                       deadline) for idle clock jumps
    tick()                             post-event hook (hedging)
    on_release(items)                  items just released into the ready
                                       queue (the engine stamps the time)
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
from collections import deque
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.analysis import sanitize as _sanitize
from repro.common import tracing

from .baselines import Policy, pad_batch, pad_bucket
from .optimizer import DualState


@dataclasses.dataclass(frozen=True)
class AdmissionRule:
    """The paper §4.2 capacity rule, deduplicated out of the simulator and
    the engine: batch size and in-flight cap both default to half the
    pool's total concurrency."""

    batch_size: int = 0      # 0 -> cap_total // 2
    max_inflight: int = 0    # 0 -> cap_total // 2

    def resolve(self, cap_total: int) -> "AdmissionRule":
        half = max(1, int(cap_total) // 2)
        return AdmissionRule(self.batch_size or half,
                             self.max_inflight or half)

    def take(self, queued: int, inflight: int) -> int:
        """How many queries the next routing window may admit."""
        return max(0, min(self.batch_size, queued,
                          self.max_inflight - inflight))


class AdaptiveWindow:
    """Adaptive routing-window width: hold the routing overhead near a
    target (carried from the streaming PR's open item).

    Each routed window runs a dual solve whose cost shows up as that
    window's ``dual_iters``; the window width trades that overhead against
    admission latency.  After every window: a solve past ``target_iters``
    WIDENS the window (more queries amortize one solve), a cheap solve
    left with a backlog deeper than ``deep_queue`` NARROWS it (admission
    is falling behind a cheap router).  Width stays clamped to
    ``[lo, hi]``."""

    def __init__(self, window: float, *, lo: float = 1.0, hi: float = 64.0,
                 target_iters: int = 50, deep_queue: int = 16,
                 grow: float = 1.5, shrink: float = 2 / 3):
        if not (0 < lo <= window <= hi):
            raise ValueError(f"need 0 < lo <= window <= hi, got "
                             f"{lo} / {window} / {hi}")
        if not (shrink < 1.0 < grow):
            raise ValueError(f"need shrink < 1 < grow, got {shrink}/{grow}")
        self.window = float(window)
        self.lo = float(lo)
        self.hi = float(hi)
        self.target_iters = int(target_iters)
        self.deep_queue = int(deep_queue)
        self.grow = float(grow)
        self.shrink = float(shrink)
        self.widened = 0
        self.narrowed = 0

    def update(self, iters_run: int, queue_depth: int) -> float:
        """Fold one routed window's observed cost + backlog; returns the
        width the NEXT window should use."""
        if iters_run > self.target_iters:
            nxt = min(self.window * self.grow, self.hi)
            self.widened += int(nxt != self.window)
            self.window = nxt
        elif (iters_run < self.target_iters // 2
                and queue_depth > self.deep_queue):
            nxt = max(self.window * self.shrink, self.lo)
            self.narrowed += int(nxt != self.window)
            self.window = nxt
        return self.window


class StreamController:
    """Routing side of the stream: persistent dual state + horizon shares.

    One controller lives for the whole stream; each routed window updates
    ``state`` (multipliers + cumulative ledger) and the iteration/window
    counters used by the benchmarks.  ``horizon`` is the expected total
    stream length — window k's budget share is ``n_k / remaining``, so a
    stationary stream spreads the global budget evenly and under-spend
    rolls forward.
    """

    def __init__(self, policy: Policy, *, horizon: int = 0,
                 stream: bool = True, rng=None, health=None,
                 adapt_window: Optional[AdaptiveWindow] = None):
        self.policy = policy
        self.stream = stream
        self.horizon = int(horizon)
        self.rng = rng
        self.health = health    # optional HealthTracker (failure plane)
        self.adapt_window = adapt_window  # optional adaptive window sizing
        self.state: Optional[DualState] = None
        self.routed = 0
        self.windows = 0
        self._iters0 = int(getattr(policy, "dual_iters", 0))

    def route(self, ds_like, loads, counts) -> np.ndarray:
        """Build the RouteBatch from the admitted queries + LIVE fleet
        state and route it — the one admission/routing path shared by the
        simulator and the engine.

        Policies that declare ``pads_windows`` (the dual controller, whose
        ``route_window`` carries a mask-aware ledger) get their windows
        padded to power-of-two buckets — multiples of the policy's
        ``window_multiple()`` under a query mesh, so sharded windows divide
        evenly across devices — and the padded rows are masked out via
        ``n_valid`` and sliced off the returned assignment.  The fused
        window jit therefore compiles O(log N) distinct shapes instead of
        one per window size.

        Ledger caveat: ``route_window`` charges the ledger for every query
        it ROUTES; a query the executor then rejects (no capacity) and
        re-routes later would be charged twice.  This is unreachable for
        the dual controller itself — it routes against ``batch.available``
        and ``repair_workload`` enforces it exactly — but a custom
        stateful policy that over-commits capacity would drift."""
        with tracing.span("route"):
            if self.health is not None:
                # breakers fold into the workload constraint (OPEN -> capacity
                # 0, HALF_OPEN -> probe slots), so the solver simply can't
                # assign to a tripped endpoint; latency EWMAs reprice the cost
                # column (multiplier >= 1: the ledger only over-estimates).
                loads = self.health.effective_loads(loads)
            if self.stream:
                batch = ds_like.route_batch(
                    np.asarray(loads, float), counts,
                    with_truth=getattr(self.policy, "needs_truth", False))
                if self.health is not None:
                    pm = self.health.price_multiplier()
                    if np.any(pm != 1.0):
                        batch = dataclasses.replace(
                            batch,
                            price_in=(batch.price_in * pm).astype(
                                batch.price_in.dtype),
                            price_out=(batch.price_out * pm).astype(
                                batch.price_out.dtype))
                n_true = batch.n
                n_rem = max(self.horizon - self.routed, n_true)
                state_in = self.state
                if getattr(self.policy, "pads_windows", False):
                    mult = getattr(self.policy, "window_multiple",
                                   lambda: 1)()
                    batch = pad_batch(batch, pad_bucket(n_true, mult))
                    x, self.state = self.policy.route_window(
                        batch, self.state, share=n_true / n_rem, rng=self.rng,
                        n_valid=n_true)
                    x = np.asarray(x)[:n_true]
                else:
                    x, self.state = self.policy.route_window(
                        batch, self.state, share=n_true / n_rem, rng=self.rng)
                if (_sanitize.active("ledgersan") and state_in is not None
                        and self.state is not None):
                    # host-level ledger monotonicity across the window — covers
                    # the fused predict->solve path the solver-level hook must
                    # skip (everything is a tracer inside the jit)
                    _sanitize.check_state_monotone(state_in, self.state,
                                                   where="StreamController")
                n_routed = n_true
            else:
                from .scheduler import route_via_batch
                x = route_via_batch(self.policy, ds_like, loads, counts,
                                    rng=self.rng)
                n_routed = len(x)
            self.routed += n_routed
            self.windows += 1
        return np.asarray(x).astype(int)

    @property
    def dual_iters(self) -> int:
        """Dual iterations run on THIS stream (policies accumulate across
        their lifetime; the baseline was captured at construction)."""
        return int(getattr(self.policy, "dual_iters", 0)) - self._iters0


class FoldBuffer:
    """Buffered online fold-back of completions into the policy's store
    (``fold_completions``), shared by both drivers.  ``features`` maps a
    list of completed items to a dataset-like with ``queries`` /
    ``correct`` / ``out_len`` (the same producer used for admission)."""

    def __init__(self, policy: Policy, features: Callable, *,
                 enabled: bool = False, chunk: int = 64):
        self.policy = policy
        self.features = features
        self.enabled = enabled
        self.chunk = max(1, chunk)
        self.buf: List = []
        self.folded = 0

    def add(self, items: Sequence):
        if self.enabled:
            self.buf.extend(items)

    def flush(self, force: bool = False):
        if not self.enabled or not self.buf:
            return
        if not force and len(self.buf) < self.chunk:
            return
        from .scheduler import fold_completions
        with tracing.span("fold"):
            if fold_completions(self.policy, self.features(self.buf),
                                np.arange(len(self.buf))):
                self.folded += len(self.buf)
        self.buf.clear()


class ControlLoop:
    """The shared admit→advance loop.

    ``items`` are opaque to the loop (the simulator uses query indices, the
    engine uses Requests); ``arrival_times`` releases them into the ready
    queue as the executor's clock passes each time (None = all at t=0, the
    pre-streaming behavior).  ``window`` > 0 rate-limits routing windows:
    a window fires when at least ``window`` clock units have passed since
    the last one OR a full batch has accumulated, so light traffic batches
    up instead of degenerating to per-query routing.

    ``drain_admissions`` mirrors the drivers' historical cadence: the
    event-driven simulator admits back-to-back windows while capacity
    lasts before processing the next completion; the engine interleaves
    one admission per decode step.
    """

    def __init__(self, *, executor, controller: StreamController,
                 rule: AdmissionRule, items: Sequence,
                 features: Callable, fold: FoldBuffer,
                 arrival_times: Optional[np.ndarray] = None,
                 window: float = 0.0, drain_admissions: bool = True,
                 requeue_front: bool = False, health=None):
        self.executor = executor
        self.controller = controller
        self.rule = rule
        self.features = features
        self.fold = fold
        self.window = float(window)
        self.drain_admissions = drain_admissions
        self.requeue_front = requeue_front
        self.health = health
        self._seq = itertools.count()
        items = list(items)
        if arrival_times is None:
            arrival_times = np.zeros(len(items))
        order = np.argsort(arrival_times, kind="stable")
        # min-heap of (time, tiebreak, item).  The tiebreak makes the pop
        # order of equal-time entries deterministic regardless of insertion
        # order — retries requeued by the executors land here, and the
        # racecheck explorer permutes the event order that produces them.
        self.pending: list = [(float(arrival_times[i]), self._pkey(items[i]),
                               items[i]) for i in order]
        heapq.heapify(self.pending)
        self.ready: deque = deque()
        self._next_window = -np.inf
        if hasattr(executor, "requeue"):
            # failed-request re-entry: the executor hands (item, at) back to
            # the admission queue with its backoff-deferred release time
            executor.requeue = self.push_pending

    def _pkey(self, item):
        rid = getattr(item, "rid", None)
        if rid is not None:
            return (0, int(rid))
        try:
            return (0, int(item))
        except (TypeError, ValueError):
            return (1, next(self._seq))

    def push_pending(self, item, at: float):
        """Re-enter ``item`` into the arrival stream at time ``at`` (retry
        after a fault, with backoff already folded into ``at``)."""
        heapq.heappush(self.pending, (float(at), self._pkey(item), item))

    # -- stream bookkeeping ----------------------------------------------------
    def _release_arrivals(self):
        with tracing.span("loop.release"):
            now = self.executor.now()
            released = []
            while self.pending and self.pending[0][0] <= now + 1e-9:
                released.append(heapq.heappop(self.pending)[2])
            if released:
                self.ready.extend(released)
                self.executor.on_release(released)

    def _wake_at(self) -> Optional[float]:
        """Next clock value at which something new can happen while the
        executor is otherwise idle: an arrival, a window deadline, or a
        breaker cooldown expiry.  Only STRICTLY FUTURE times count — a
        deadline already passed must not short-circuit the executor's own
        event processing (that would spin the loop without advancing)."""
        now = self.executor.now()
        wake = self.pending[0][0] if self.pending else None
        if (self.ready and self.window > 0 and self._next_window > now
                and (wake is None or self._next_window < wake)):
            wake = self._next_window
        if self.health is not None:
            hb = self.health.next_wake(now)
            if hb is not None and (wake is None or hb < wake):
                wake = hb
        return wake

    # -- one admission attempt -------------------------------------------------
    def _try_admit(self) -> bool:
        ex = self.executor
        if not self.ready:
            return False
        counts = np.asarray(ex.counts())
        loads = np.asarray(ex.loads())
        if self.health is not None:
            loads = self.health.effective_loads(loads)
        if not np.any(counts < loads):
            return False
        if (self.window > 0 and ex.now() < self._next_window
                and len(self.ready) < self.rule.batch_size):
            return False    # wait for the window timer (or a full batch)
        take = self.rule.take(len(self.ready), int(counts.sum()))
        if take <= 0:
            return False
        batch = [self.ready.popleft() for _ in range(take)]
        iters0 = self.controller.dual_iters
        x = self.controller.route(self.features(batch), loads, counts)
        aw = self.controller.adapt_window
        if aw is not None and self.window > 0:
            # widen/narrow the NEXT window from this one's solve cost and
            # the backlog it left behind
            self.window = aw.update(self.controller.dual_iters - iters0,
                                    len(self.ready))
        rejected = ex.dispatch(batch, x)
        for item in (reversed(rejected) if self.requeue_front else rejected):
            if self.requeue_front:
                self.ready.appendleft(item)
            else:
                self.ready.append(item)
        self._next_window = ex.now() + self.window
        ex.tick()
        # a fully-rejected batch is NOT admission progress: with
        # drain_admissions the caller would skip ``advance`` and re-route
        # the same batch against a frozen clock forever (a rate-limited
        # endpoint that looks free to the workload constraint triggers
        # exactly this).  Let the executor advance to the next event
        # instead — the items are back in ``ready`` for the next window.
        return len(rejected) < len(batch)

    # -- the loop --------------------------------------------------------------
    def run(self):
        ex = self.executor
        self._release_arrivals()
        while self.ready or self.pending or ex.counts().sum() > 0:
            with tracing.span("loop.iter"):
                if getattr(ex, "stopped", False):
                    break           # executor hit its hard step budget
                if self.health is not None:
                    self.health.advance(ex.now())  # OPEN -> HALF_OPEN
                admitted = self._try_admit()
                if admitted and self.drain_admissions:
                    continue
                done, progressed = ex.advance(self._wake_at())
                if done:
                    self.fold.add(done)
                    self.fold.flush()
                ex.tick()
                self._release_arrivals()
                if not progressed and not admitted:
                    break           # deadlocked or out of steps: bail
        self.fold.flush(force=True)
        return self
