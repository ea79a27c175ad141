"""The four readers of the program's recorder on hand-built records.

The window opens at perf_counter 100 s and lasts 10 s; the trace starts
5 s in, so every reader takes [101 s, 105 s).
"""
import importlib
import sys
import types

import pytest

from bench.clock import Window
from repro.common import tracing

READERS = ("loop_iter_p50_ms", "queue_wait_p50_ms",
           "first_token_wait_p50_ms", "member_slot_use")
MAIN = 2


def ctx(traced_at=5.0):
    return types.SimpleNamespace(win=Window(
        seconds=10.0, drain_s=0.0, main=MAIN, t_open=100.0,
        traced=(traced_at, traced_at + 4.0)))


def read(name, c=None):
    return importlib.import_module(f"bench.metrics.{name}").read(c or ctx())


@pytest.fixture
def recorder():
    tracing.reset()
    yield tracing
    tracing.reset()


def spans(*rows):
    for i, (name, start, end, rid) in enumerate(rows):
        tracing.spans.append((i, name, start, end, None, rid))


def request(rid, released, admitted, first):
    spans(("request.queue", released, admitted, rid),
          ("request.first", admitted, first, rid))


def test_loop_iter_takes_whole_passes_in_range(recorder):
    spans(("loop.iter", 100.5, 100.9, None),      # before 10% of the window
          ("loop.iter", 101.0, 101.2, None),
          ("loop.iter", 102.0, 102.3, None),
          ("loop.iter", 103.0, 103.4, None),
          ("loop.iter", 104.9, 105.5, None),      # runs past the trace start
          ("loop.release", 103.0, 103.1, None))
    assert read("loop_iter_p50_ms") == pytest.approx(300.0)


def test_queue_wait_takes_requests_released_in_range(recorder):
    request(0, 100.9, 101.9, 102.0)               # released too early
    request(1, 101.0, 101.01, 101.3)
    request(2, 102.0, 102.03, 102.5)
    request(3, 104.0, 104.05, 104.2)
    request(4, 105.0, 105.5, 105.6)               # released at the trace
    assert read("queue_wait_p50_ms") == pytest.approx(30.0)


def test_first_token_wait_takes_the_same_requests(recorder):
    request(0, 100.9, 101.0, 102.0)               # released too early
    request(1, 101.0, 101.01, 101.21)
    request(2, 102.0, 102.03, 102.33)
    request(3, 104.9, 104.95, 105.35)             # first token after the
    #                                               trace start still counts
    request(4, 105.0, 105.1, 105.2)               # released at the trace
    spans(("request.first", 102.0, 109.0, 2))     # an earlier attempt's
    assert read("first_token_wait_p50_ms") == pytest.approx(300.0)


def chunk(t, tokens, steps, label=MAIN):
    """One chunk's growth of the two counts, as the engine records it."""
    tracing.samples.append(("tokens", label, t, tokens))
    tracing.samples.append(("slot_steps", label, t, steps))


def test_member_slot_use_is_the_members_growth_in_range(recorder):
    for t, tok in ((100.5, 10), (102.0, 30), (104.0, 50), (106.0, 100)):
        chunk(t, tok, 100)
        chunk(t, 0, 100, label=MAIN + 1)
    assert read("member_slot_use") == pytest.approx(100.0 * 80 / 200)


def test_member_slot_use_without_an_earlier_sample(recorder):
    """The first chunk in range counts in full, whatever came before."""
    chunk(102.0, 40, 100)
    chunk(104.0, 50, 100)
    assert read("member_slot_use") == pytest.approx(45.0)


@pytest.mark.parametrize("name", READERS)
def test_none_when_the_range_is_empty(recorder, name):
    request(1, 101.0, 101.01, 101.3)
    spans(("loop.iter", 101.0, 101.2, None))
    chunk(101.5, 4, 8)
    assert read(name, ctx(traced_at=1.0)) is None   # trace at 10%
    tracing.reset()
    assert read(name) is None                       # nothing recorded


@pytest.mark.parametrize("name", READERS)
def test_none_without_the_recorder(recorder, monkeypatch, name):
    """A program that has no ``repro.common.tracing`` reads as nothing."""
    import repro.common
    request(1, 101.0, 101.01, 101.3)
    spans(("loop.iter", 101.0, 101.2, None))
    chunk(101.5, 4, 8)
    assert read(name) is not None
    monkeypatch.setitem(sys.modules, "repro.common.tracing", None)
    monkeypatch.delattr(repro.common, "tracing")
    assert read(name) is None
