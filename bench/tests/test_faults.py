"""The harness, with the timed path broken underneath, says not correct.

Drives ``bench.run`` as a run does (set-up, an open-loop window, the
reference checks) with the program's smoke member in place of the
full-width one and no look for a chip, once sound and once for each fault
a serving cell can have: a token altered where the decode chunk produces
it; a decode chunk that returns its state unchanged; requests cut short.
"""
import numpy as np
import pytest

from bench import check, run

CELL = "omni6-danube3-4b.chat"
SECONDS = 3.0


@pytest.fixture(scope="module")
def cell():
    run.chip(1, rehearse=True)
    c = run.Cell(CELL, 5, rehearse=True)
    sched = c.schedule(2.0, SECONDS)
    c.warm([sched])
    return c, sched


def numbers(c, sched):
    win, done, _ = c.serve(sched, SECONDS)
    failed = run.client_metrics(sched, win, done, SECONDS)["failed"]
    return c.numbers(win, c.served(sched, done), failed)[0]


def broken_chunk(chunk, fault):
    import jax
    import jax.numpy as jnp

    def wrapped(params, state, *rest):
        if fault == "token":
            new_state, last, lens, rem, toks = chunk(params, state, *rest)
            toks = toks.at[:, 0].set((toks[:, 0] + 1) % 512)
            return new_state, last, lens, rem, toks
        # the chunk donates its state: run it on a copy, keep the input
        _, last, lens, rem, toks = chunk(params, jax.tree.map(jnp.copy, state),
                                         *rest)
        return state, last, lens, rem, toks
    return wrapped


def test_sound_run_is_correct(cell):
    c, sched = cell
    got = numbers(c, sched)
    assert np.isfinite(got["logit_gap"]), "the member served nothing"
    assert check.verdict(got, c.params["limits"]), got


@pytest.mark.parametrize("fault", ["token", "state"])
def test_fault_is_not_correct(cell, fault):
    c, sched = cell
    ep = c.endpoints[c.main]
    chunk = ep._chunk
    ep._chunk = broken_chunk(chunk, fault)
    try:
        assert not check.verdict(numbers(c, sched), c.params["limits"])
    finally:
        ep._chunk = chunk


def test_cut_short_is_not_correct(cell):
    """The member admits each request with half the tokens it asked for."""
    c, sched = cell
    ep = c.endpoints[c.main]
    admit = ep.admit

    def halved(req):
        req.max_new = max(1, req.max_new // 2)
        return admit(req)
    ep.admit = halved
    try:
        got = numbers(c, sched)
        assert got["failed"] > 0, got
        assert not check.verdict(got, c.params["limits"])
    finally:
        ep.__dict__.pop("admit", None)
