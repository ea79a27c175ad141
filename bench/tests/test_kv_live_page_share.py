"""The ``kv_live_page_share`` reader on hand-built records, in the window of
``test_loop_metrics``: it opens at perf_counter 100 s and lasts 10 s, the
trace starts 5 s in, so the reader takes [101 s, 105 s)."""
import sys

import pytest

from repro.common import tracing

from test_loop_metrics import MAIN, ctx, read


@pytest.fixture
def recorder():
    tracing.reset()
    yield tracing
    tracing.reset()


def chunk(t, pages, table, label=MAIN):
    """One chunk's growth of the two counts, as the engine records it."""
    tracing.samples.append(("kv_pages", label, t, pages))
    tracing.samples.append(("kv_pages_table", label, t, table))


def test_share_is_the_members_growth_in_range(recorder):
    for t, pages in ((100.5, 10), (102.0, 30), (104.0, 50), (106.0, 100)):
        chunk(t, pages, 400)
        chunk(t, 400, 400, label=MAIN + 1)
    tracing.samples.append(("tokens", MAIN, 103.0, 999))
    assert read("kv_live_page_share") == pytest.approx(100.0 * 80 / 800)


def test_none_when_the_range_is_empty(recorder):
    chunk(101.5, 4, 8)
    assert read("kv_live_page_share", ctx(traced_at=1.0)) is None
    tracing.reset()
    tracing.samples.append(("tokens", MAIN, 102.0, 8))   # a program that
    tracing.samples.append(("slot_steps", MAIN, 102.0, 8))  # counts no pages
    assert read("kv_live_page_share") is None


def test_none_without_the_recorder(recorder, monkeypatch):
    """A program that has no ``repro.common.tracing`` reads as nothing."""
    import repro.common
    chunk(101.5, 4, 8)
    assert read("kv_live_page_share") == pytest.approx(50.0)
    monkeypatch.setitem(sys.modules, "repro.common.tracing", None)
    monkeypatch.delattr(repro.common, "tracing")
    assert read("kv_live_page_share") is None
