"""The in-program recorder (``repro.common.tracing``) on a streamed smoke
pool: request stage stamps, the span tree of the serving loop, the counts
against the endpoints' own, the ring's bound, what the garbage collector
keeps tracking, and the span clock against the profiler's."""
import collections
import gc
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.common import tracing
from repro.configs import get_smoke_config
from repro.core import OmniRouter, RetrievalPredictor, RouterConfig
from repro.data import tokenizer
from repro.data.qaserve import generate
from repro.launch.serve import POOL_ARCHS, build_server
from repro.serving.engine import Request

N_REQUESTS = 12

Span = collections.namedtuple("Span", "sid name start end parent rid")


@pytest.fixture(scope="module")
def served():
    """One streamed run of the six-endpoint smoke pool (sized like
    ``tests/test_chip_smoke.py``), arrivals spread over the first steps,
    recorded from an empty recorder.  Returns (server, done, spans, the
    summed growth of each (count, label), route seconds the run's spans
    add up to)."""
    train, _, test = generate(n=1200, seed=0).split()
    test = test.subset(np.arange(N_REQUESTS))
    router = OmniRouter(RetrievalPredictor(k=8).fit(train),
                        RouterConfig(alpha=0.5), name="ECCOS-R")
    cfgs = [get_smoke_config(a) for a in POOL_ARCHS]
    server = build_server(cfgs, router, max_concurrency=2, t_max=48,
                          stream=True, horizon=test.n)
    vocab_cfg = min(cfgs, key=lambda c: c.vocab_size)
    for i in range(test.n):
        toks = tokenizer.encode_for_config(vocab_cfg, test.queries[i], 64)
        server.submit(Request(rid=i, tokens=np.resize(toks, 33), max_new=4),
                      at_step=float(i // 3))
    tracing.reset()
    done = server.run(lambda batch: test.subset(
        np.array([r.rid for r in batch])))
    route_s = tracing.total("route")
    counts: dict = {}
    for name, label, _, n in tracing.samples:
        counts[(name, label)] = counts.get((name, label), 0) + n
    return (server, done, [Span(*s) for s in tracing.spans], counts, route_s)


def test_every_request_served_with_ordered_stages(served):
    _, done, _, _, _ = served
    assert sorted(r.rid for r in done) == list(range(N_REQUESTS))
    for r in done:
        assert not r.failed and len(r.output) == 4
        assert 0 < r.released <= r.admitted <= r.first_token <= r.finished, r


def test_request_records_match_stamps(served):
    _, done, spans, _, _ = served
    by_rid = {r.rid: r for r in done}
    for name, lo, hi in (("request.queue", "released", "admitted"),
                         ("request.first", "admitted", "first_token")):
        recs = [s for s in spans if s.name == name]
        assert sorted(s.rid for s in recs) == list(range(N_REQUESTS))
        for s in recs:
            r = by_rid[s.rid]
            assert (s.start, s.end) == (getattr(r, lo), getattr(r, hi))


@pytest.mark.parametrize("prefix", ["chunk.dispatch.", "chunk.fetch.",
                                    "chunk.emit.", "admit."])
def test_endpoint_spans_sit_in_a_loop_iteration(served, prefix):
    """Each endpoint span's parent is the ``loop.iter`` that holds it."""
    _, _, spans, _, _ = served
    by_sid = {s.sid: s for s in spans}
    mine = [s for s in spans if s.name.startswith(prefix)
            and not s.name.startswith(("admit.prefill.", "admit.write."))]
    assert mine
    for s in mine:
        parent = by_sid[s.parent]
        assert parent.name == "loop.iter", (s, parent)
        assert parent.start <= s.start <= s.end <= parent.end


def test_admission_children_and_self_times(served):
    _, _, spans, _, _ = served
    by_sid = {s.sid: s for s in spans}
    for s in spans:
        if s.name.startswith(("admit.prefill.", "admit.write.")):
            assert by_sid[s.parent].name.startswith("admit.")
            assert by_sid[s.parent].rid is not None
    child_s: dict = {}
    for s in spans:
        if s.parent is not None:
            child_s[s.parent] = child_s.get(s.parent, 0.0) + s.end - s.start
    for s in spans:
        assert s.end - s.start - child_s.get(s.sid, 0.0) >= 0.0, s


@pytest.mark.parametrize("counter,attr", [("tokens", "decoded_tokens"),
                                          ("slot_steps", "busy_steps")])
def test_endpoint_counters_equal_owners(served, counter, attr):
    """The tokens and slot-steps recorded per chunk add up to the tokens the
    endpoint counts and to its chunks times slots times steps."""
    server, _, _, counts, _ = served
    scale = {"tokens": lambda ep: 1,
             "slot_steps": lambda ep: ep.L * ep.sync_every}[counter]
    for ep in server.endpoints:
        assert counts.get((counter, ep.label), 0) == \
            getattr(ep, attr) * scale(ep)
    assert sum(getattr(ep, attr) for ep in server.endpoints) > 0


def test_loop_counters_and_route_seconds(served):
    server, _, spans, _, route_s = served
    names = [s.name for s in spans]
    assert names.count("route") == server.route_calls > 0
    assert names.count("loop.iter") > 0
    assert server.route_seconds == pytest.approx(route_s)
    assert route_s == pytest.approx(sum(s.end - s.start for s in spans
                                        if s.name == "route"))


def test_spec_pair_stamps_and_counts():
    """A (draft, verify) pair's rounds stamp the first token and record the
    verify endpoint's tokens and both endpoints' slot-steps."""
    from repro.core.speculative import SpecPair
    from repro.serving.engine import Endpoint, MultiLLMServer
    cfg = get_smoke_config("h2o-danube-3-4b")
    eps = [Endpoint(cfg, max_concurrency=2, t_max=48, seed=s, page_size=8,
                    sync_every=4) for s in (7, 0)]
    srv = MultiLLMServer(eps, policy=None, spec_pairs=(SpecPair(0, 1, k=3),))
    ex = srv._executor_cls(srv, max_steps=1000)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, tokens=rng.randint(1, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new=5) for i, n in enumerate((5, 9))]
    tracing.reset()
    for r in reqs:
        srv.admit_spec(r, 0)
    while srv._spec:
        ex.advance(None)
    for r in reqs:
        assert r.done and len(r.output) == 5
        assert 0 < r.admitted <= r.first_token <= r.finished, r
    grew: dict = {}
    for name, label, _, n in tracing.samples:
        grew[(name, label)] = grew.get((name, label), 0) + n
    d_ep, v_ep = eps
    assert grew[("tokens", v_ep.label)] == v_ep.decoded_tokens == 10
    assert ("tokens", d_ep.label) not in grew
    for ep in eps:
        assert grew[("slot_steps", ep.label)] == ep.busy_steps * ep.L * 3
    tracing.reset()


@pytest.mark.parametrize("ring", ["spans", "samples"])
def test_ring_stays_bounded(ring):
    tracing.reset()
    n = tracing.RING + 100
    for i in range(n):
        if ring == "spans":
            with tracing.span("bounded", i):
                pass
        else:
            tracing.count("bounded", 0, i)
    kept = getattr(tracing, ring)
    assert len(kept) == tracing.RING
    # the last field: a span's rid, a sample's growth
    assert (kept[0][-1], kept[-1][-1]) == (n - tracing.RING, n - 1)
    tracing.reset()


def test_records_leave_the_collectors_tracking():
    """Records are plain tuples of numbers, strings and None: once a
    collection has seen them the collector stops tracking them, so a full
    ring adds nothing to later collections."""
    tracing.reset()
    for i in range(100):
        with tracing.span("loop.iter"):
            with tracing.span(f"admit.{i % 6}", i):
                pass
        tracing.record("request.queue", 1.0, 2.0, i)
        tracing.count("tokens", i % 6, 8)
    gc.collect()
    for ring in (tracing.spans, tracing.samples):
        assert ring
        assert not any(gc.is_tracked(r) for r in ring)
    tracing.reset()


def test_span_maps_onto_profiler_clock(tmp_path):
    """The profiler dates each span's ``TraceAnnotation`` on its own clock;
    one offset per trace maps it onto the span's ``perf_counter`` stamp."""
    from jax.profiler import ProfileData
    tracing.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(5):
            with tracing.span(f"clock.probe.{i}"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    traced = {e.name: e.start_ns for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("clock.probe.")}
    mine = {s[1]: s[2] for s in tracing.spans}
    assert sorted(traced) == [f"clock.probe.{i}" for i in range(5)]
    offsets = [mine[n] * 1e9 - traced[n] for n in traced]
    assert max(offsets) - min(offsets) <= 50e3, offsets
    tracing.reset()
