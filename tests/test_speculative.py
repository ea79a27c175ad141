"""Speculative cascade plane (ISSUE 10): (draft, verify) pair columns in
the solver, the acceptance EWMAs that reprice them, and the engine's
draft/verify rounds on the paged KV pool.

Covers the acceptance criteria end to end: greedy speculative decode is
BIT-identical to strong-only decode (even under a junk draft that accepts
almost nothing); pair columns compose with warm starts, the streaming
ledger, robust LCB solves, and the 8-virtual-device query mesh; rejected
draft pages drain through the normal allocator path under PageSan; and
``Endpoint.compile_count()`` stays constant across speculative churn.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (AcceptanceTracker, AdaptiveWindow, DualSolver,
                        SpecPair, expand_pair_columns, init_dual_state,
                        pair_index_arrays)
from repro.core.speculative import ACC_EPS

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# pair columns: shapes, pricing, P=0 neutrality
# ---------------------------------------------------------------------------

def test_spec_pair_validation():
    with pytest.raises(ValueError):
        SpecPair(1, 1)                      # draft == verify
    with pytest.raises(ValueError):
        SpecPair(0, 1, k=0)                 # k < 1
    assert SpecPair(0, 1).k == 4            # paper default


def test_expand_pair_columns_pricing_and_p0_identity():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    cost = jnp.asarray(rng.uniform(0.1, 2.0, (16, 4)).astype(np.float32))
    qual = jnp.asarray(rng.uniform(0.0, 1.0, (16, 4)).astype(np.float32))
    # P = 0 is bit-neutral: the very same arrays come back
    c0, q0 = expand_pair_columns(cost, qual, (), (), None)
    assert c0 is cost and q0 is qual
    # P = 2: pair p costs c_d + c_v / e_acc and carries verify's quality
    pairs = (SpecPair(0, 3, k=4), SpecPair(1, 2, k=2))
    didx, vidx = pair_index_arrays(pairs)
    e = np.array([2.5, 0.01], np.float32)   # second EWMA below the floor
    c1, q1 = expand_pair_columns(cost, qual, didx, vidx, jnp.asarray(e))
    assert c1.shape == (16, 6) and q1.shape == (16, 6)
    assert np.array_equal(np.asarray(c1[:, :4]), np.asarray(cost))
    assert np.allclose(np.asarray(c1[:, 4]),
                       np.asarray(cost[:, 0] + cost[:, 3] / 2.5))
    # a dead draft saturates at ACC_EPS instead of dividing by ~0
    assert np.allclose(np.asarray(c1[:, 5]),
                       np.asarray(cost[:, 1] + cost[:, 2] / ACC_EPS))
    assert np.array_equal(np.asarray(q1[:, 4]), np.asarray(qual[:, 3]))
    assert np.array_equal(np.asarray(q1[:, 5]), np.asarray(qual[:, 2]))


def test_acceptance_tracker_ewma_and_clipping():
    pairs = (SpecPair(0, 1, k=4), SpecPair(2, 1, k=2))
    acc = AcceptanceTracker(pairs, beta=0.5)
    # uninformative prior: midpoint of [1, k]
    assert np.allclose(acc.expected(), [2.5, 1.5])
    acc.record(0, 4.0)
    assert np.allclose(acc.expected()[0], 0.5 * 2.5 + 0.5 * 4.0)
    # n_emit outside [1, k] clips before folding
    acc.record(1, 99.0)
    assert np.allclose(acc.expected()[1], 0.5 * 1.5 + 0.5 * 2.0)
    acc.record(1, -3.0)
    assert acc.expected()[1] >= 1.0 or acc.expected()[1] >= ACC_EPS
    assert list(acc.rounds) == [1, 2]
    # expected() is a copy — callers can't mutate tracker state through it
    view = acc.expected()
    view[:] = 0.0
    assert acc.expected()[0] > 0.0


class _StubPredictor:
    """Host-path predictor returning fixed (cap, cost) arrays."""

    def __init__(self, cap, cost):
        self._cap, self._cost = cap, cost

    def predict_arrays(self, batch):
        return self._cap, None, self._cost


def _pair_batch(n, m, p, seed=0):
    from repro.core.baselines import RouteBatch
    rng = np.random.default_rng(seed)
    cap = rng.uniform(0.0, 1.0, (n, m)).astype(np.float32)
    cost = (rng.uniform(0.2, 3.0, (n, m)) * 1e-3).astype(np.float32)
    batch = RouteBatch(queries=["q"] * n, input_len=np.ones(n),
                       price_in=np.ones(m), price_out=np.ones(m),
                       loads=np.full(m + p, float(n)),
                       counts=np.zeros(m + p))
    return batch, cap, cost


def test_route_window_pair_columns_match_manual_expansion():
    """The router's pair-column window == predict -> expand -> solve done
    by hand: same assignment bits, same (M+P)-axis ledger state."""
    import jax.numpy as jnp
    from repro.core import OmniRouter, RouterConfig
    pairs = (SpecPair(0, 2, k=4),)
    batch, cap, cost = _pair_batch(64, 3, len(pairs))
    cfg = RouterConfig(alpha=0.55, spec_pairs=pairs)
    router = OmniRouter(_StubPredictor(cap, cost), cfg)
    x, state = router.route_window(batch, None)
    assert state.lam_load.shape == (3 + len(pairs),)

    didx, vidx = pair_index_arrays(pairs)
    e_acc = jnp.asarray(router.acceptance.expected(), jnp.float32)
    c2, q2 = expand_pair_columns(jnp.asarray(cost), jnp.asarray(cap),
                                 didx, vidx, e_acc)
    x_ref, _, st_ref = router.stream_solver.route_window(
        c2, q2, cfg.alpha, jnp.asarray(batch.available),
        init_dual_state(3 + len(pairs)), share=1.0,
        polish_margin=cfg.alpha_margin)
    assert np.array_equal(x, np.asarray(x_ref))
    assert float(state.budget_spent) == float(st_ref.budget_spent)
    # the solver actually uses the pair column when it prices well
    assert x.max() < 3 + len(pairs)


def test_acceptance_repricing_moves_pair_cost_without_retracing():
    """Recording verify rounds moves expected() and hence the pair price;
    the EWMA enters the fused window as a runtime array, so two windows at
    different EWMAs reuse one compiled program (windows counter advances,
    assignments may differ, no error from a retrace guard)."""
    from repro.core import OmniRouter, RouterConfig
    pairs = (SpecPair(0, 1, k=4),)
    batch, cap, cost = _pair_batch(32, 2, 1, seed=3)
    router = OmniRouter(_StubPredictor(cap, cost),
                        RouterConfig(alpha=0.5, spec_pairs=pairs))
    e0 = router.acceptance.expected().copy()
    _, state = router.route_window(batch, None)
    for _ in range(6):
        router.acceptance.record(0, 4.0)    # perfect acceptance
    assert router.acceptance.expected()[0] > e0[0]
    _, state = router.route_window(batch, state)
    assert router.windows == 2


@pytest.mark.parametrize("mode,threshold", [("quality", 0.55),
                                            ("budget", 0.04)])
def test_pair_columns_compose_with_robust_kappa0_warm(mode, threshold):
    """robust=True, kappa=0 stays BIT-identical to the plain solve on the
    (M+P)-column pair matrices, warm across a 3-window stream."""
    import jax.numpy as jnp
    pairs = (SpecPair(0, 3, k=4), SpecPair(1, 2, k=2))
    didx, vidx = pair_index_arrays(pairs)
    rng = np.random.default_rng(0)
    n, m = 128, 4
    mp = m + len(pairs)
    loads = np.full((mp,), float(n) / mp + 4, np.float32)
    base = DualSolver(mode, iters=60, norm_grad=True, stall_tol=1e-3)
    rob = dataclasses.replace(base, robust=True, kappa=0.0)
    st0 = st1 = init_dual_state(mp)
    e_acc = jnp.asarray([2.0, 1.25], jnp.float32)
    for _ in range(3):
        cost = (rng.uniform(0.2, 3.0, (n, m)) * 1e-3).astype(np.float32)
        qual = rng.uniform(0.0, 1.0, (n, m)).astype(np.float32)
        c2, q2 = expand_pair_columns(jnp.asarray(cost), jnp.asarray(qual),
                                     didx, vidx, e_acc)
        x0, i0, st0 = base.route_window(c2, q2, threshold, loads, st0)
        x1, i1, st1 = rob.route_window(c2, q2, threshold, loads, st1)
        assert bool(jnp.all(jnp.asarray(x0) == jnp.asarray(x1)))
        assert float(st0.budget_spent) == float(st1.budget_spent)
        assert float(st0.sr_deficit) == float(st1.sr_deficit)
        assert int(i0.iters_run) == int(i1.iters_run)


@pytest.mark.slow
def test_pair_columns_8dev_mesh_parity():
    """The mesh-sharded windowed solve on pair-expanded (N, M+P) matrices
    matches the single-device blocked solve warm across 3 windows: equal
    assignments and dual-step counts, the money/quality ledger and the
    multipliers to float tolerance.  The installed XLA compiles the
    one-block-per-device partial sums differently from the eight-block
    loop, so they differ in the last bits and λ drifts by ~1e-6."""
    snippet = """
        import numpy as np, jax, jax.numpy as jnp
        assert jax.device_count() == 8, jax.devices()
        from repro.common import use_mesh, query_mesh, query_rules
        from repro.core.optimizer import DualSolver, init_dual_state
        from repro.core.speculative import (SpecPair, expand_pair_columns,
                                            pair_index_arrays)
        rng = np.random.default_rng(0)
        n, m = 256, 4
        pairs = (SpecPair(0, 3, k=4), SpecPair(1, 2, k=2))
        didx, vidx = pair_index_arrays(pairs)
        e_acc = jnp.asarray([2.5, 1.5], jnp.float32)
        mp = m + len(pairs)
        loads = np.full((mp,), n / mp + 4, np.float32)
        s = DualSolver("quality", iters=60, norm_grad=True, stall_tol=1e-3,
                       shards=8)
        mesh, rules = query_mesh(8), query_rules()
        st_a = st_b = init_dual_state(mp)
        for w in range(3):
            cost = (rng.uniform(0.2, 3.0, (n, m)) * 1e-3).astype(np.float32)
            qual = rng.uniform(0.0, 1.0, (n, m)).astype(np.float32)
            c2, q2 = expand_pair_columns(jnp.asarray(cost),
                                         jnp.asarray(qual), didx, vidx,
                                         e_acc)
            xa, _, st_a = s.route_window(c2, q2, 0.55, loads, st_a)
            with use_mesh(mesh, rules):
                xb, _, st_b = s.route_window(c2, q2, 0.55, loads, st_b)
            assert np.array_equal(np.asarray(xa), np.asarray(xb)), w
            assert np.array_equal(np.asarray(st_a.steps),
                                  np.asarray(st_b.steps)), w
            for f in ("budget_spent", "sr_deficit"):
                assert np.allclose(np.asarray(getattr(st_a, f)),
                                   np.asarray(getattr(st_b, f)),
                                   rtol=1e-5, atol=0), (f, w)
            for f in ("lam", "lam_load"):
                assert np.allclose(np.asarray(getattr(st_a, f)),
                                   np.asarray(getattr(st_b, f)),
                                   rtol=1e-4, atol=1e-5), (f, w)
        print("SPEC-MESH-PARITY-OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SPEC-MESH-PARITY-OK" in out.stdout


# ---------------------------------------------------------------------------
# engine: speculative greedy == strong-only greedy, page discipline
# ---------------------------------------------------------------------------

def _spec_identity_run(arch):
    """Run 3 requests through a (junk draft, strong verify) pair and
    return (requests, reference requests, server, endpoints)."""
    from repro.configs import get_smoke_config
    from repro.serving.engine import Endpoint, MultiLLMServer, Request

    rng = np.random.RandomState(0)
    cfg = get_smoke_config(arch)
    # draft: same arch, DIFFERENT weights — acceptance is incidental, the
    # output contract must hold regardless
    d_ep = Endpoint(cfg, max_concurrency=3, t_max=64, seed=7, page_size=8,
                    sync_every=4)
    v_ep = Endpoint(cfg, max_concurrency=3, t_max=64, seed=0, page_size=8,
                    sync_every=4)
    srv = MultiLLMServer([d_ep, v_ep], policy=None,
                         spec_pairs=(SpecPair(0, 1, k=3),))
    ex = srv._executor_cls(srv, max_steps=10_000)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 11, 3)]
    reqs = [Request(rid=i, tokens=p, max_new=9 + i)
            for i, p in enumerate(prompts)]
    for r in reqs:
        srv.admit_spec(r, 0)
    cc = None
    it = 0
    while srv._spec:
        ex.advance(None)
        it += 1
        if it == 2:     # everything is compiled after the first full round
            cc = (d_ep.compile_count(), v_ep.compile_count())
        assert it < 200
    assert (d_ep.compile_count(), v_ep.compile_count()) == cc

    ref_ep = Endpoint(cfg, max_concurrency=3, t_max=64, seed=0, page_size=8,
                      sync_every=4)
    ref = [Request(rid=10 + i, tokens=p, max_new=9 + i)
           for i, p in enumerate(prompts)]
    for r in ref:
        ref_ep.admit(r)
    while ref_ep.active_count():
        ref_ep.step()
    return reqs, ref, srv, (d_ep, v_ep)


@pytest.mark.sanitize("pagesan")
def test_speculative_matches_strong_only_danube():
    """Tentpole identity on the dense-GQA family, under PageSan: the
    speculative output is BIT-identical to strong-only decode, both paged
    pools drain pristine, and compile counts are churn-constant."""
    reqs, ref, srv, (d_ep, v_ep) = _spec_identity_run("h2o-danube-3-4b")
    for r, rr in zip(reqs, ref):
        assert r.done and rr.done
        assert r.output == rr.output, (r.rid, r.output, rr.output)
    assert srv.spec_rounds > 0 and srv.spec_emitted == sum(
        r.max_new for r in reqs)
    d_ep.alloc.san.assert_drained(d_ep)
    v_ep.alloc.san.assert_drained(v_ep)


@pytest.mark.slow
@pytest.mark.sanitize("pagesan")
def test_speculative_matches_strong_only_moe():
    """Same identity on the MoE-FFN family (dbrx)."""
    reqs, ref, srv, (d_ep, v_ep) = _spec_identity_run("dbrx-132b")
    for r, rr in zip(reqs, ref):
        assert r.done and rr.done
        assert r.output == rr.output, (r.rid, r.output, rr.output)
    d_ep.alloc.san.assert_drained(d_ep)
    v_ep.alloc.san.assert_drained(v_ep)


def test_identical_weights_accept_every_draft():
    """A draft with the VERIFY model's weights agrees on every greedy token,
    so each round emits exactly k and max_new tokens take ceil(max_new/k)
    verify rounds — the amortization ceiling the pair price models."""
    from repro.configs import get_smoke_config
    from repro.serving.engine import Endpoint, MultiLLMServer, Request

    cfg = get_smoke_config("h2o-danube-3-4b")
    eps = [Endpoint(cfg, max_concurrency=2, t_max=64, seed=0, page_size=8,
                    sync_every=4) for _ in range(2)]
    srv = MultiLLMServer(eps, policy=None, spec_pairs=(SpecPair(0, 1, k=4),))
    rng = np.random.RandomState(0)
    req = Request(rid=0, tokens=rng.randint(1, cfg.vocab_size, size=5),
                  max_new=12)
    srv.admit_spec(req, 0)
    ex = srv._executor_cls(srv, 1000)
    while srv._spec:
        ex.advance(None)
    assert req.done and len(req.output) == 12
    assert srv.spec_rounds == 3          # 12 tokens / k=4
    assert srv.spec_emitted == 12


@pytest.mark.sanitize("pagesan")
def test_rollback_below_accepted_prefix_fires_pagesan():
    """Releasing a page that still backs the ACCEPTED prefix of a spec slot
    is a bug class PageSan must catch (satellite: rollback discipline)."""
    from repro.analysis.sanitize.pagesan import PageSanError
    from repro.configs import get_smoke_config
    from repro.serving.engine import Endpoint, Request

    cfg = get_smoke_config("h2o-danube-3-4b")
    ep = Endpoint(cfg, max_concurrency=2, t_max=64, seed=0, page_size=8,
                  sync_every=4)
    rng = np.random.RandomState(0)
    req = Request(rid=0, tokens=rng.randint(1, cfg.vocab_size, size=5),
                  max_new=8)
    slot = ep.admit_spec(req, k=3)
    ep.ensure_pages(slot, 17)            # 3 pages: covers base 17 tokens
    ep.lens[slot] = 17                   # accepted prefix spans all 3 pages
    with pytest.raises(PageSanError):
        ep.rollback_pages(slot, 9)       # cuts page 2 out from under it
    # the legal rollback (back to the accepted prefix boundary) is clean
    ep2 = Endpoint(cfg, max_concurrency=2, t_max=64, seed=0, page_size=8,
                   sync_every=4)
    slot2 = ep2.admit_spec(req, k=3)
    ep2.ensure_pages(slot2, 17 + 3)
    ep2.lens[slot2] = 17
    ep2.rollback_pages(slot2, 17)        # drops only the draft overhang
    ep2.release_spec(slot2)
    ep2.alloc.san.assert_drained(ep2)


def test_spec_rejects_recurrent_families_and_health_composition():
    """Recurrent/hybrid state can't roll back by dropping pages, and the
    HealthTracker's model axis doesn't span pair columns — both compose
    errors must fail loudly at construction, not corrupt state later."""
    from repro.configs import get_smoke_config
    from repro.serving.engine import Endpoint, MultiLLMServer

    att = Endpoint(get_smoke_config("h2o-danube-3-4b"), max_concurrency=2,
                   t_max=64, seed=0, page_size=8, sync_every=4)
    rec = Endpoint(get_smoke_config("xlstm-350m"), max_concurrency=2,
                   t_max=64, seed=1, page_size=8, sync_every=4)
    with pytest.raises(NotImplementedError):
        MultiLLMServer([rec, att], policy=None,
                       spec_pairs=(SpecPair(0, 1, k=3),))
    with pytest.raises(NotImplementedError):
        MultiLLMServer([att, att], policy=None, health=True,
                       spec_pairs=(SpecPair(0, 1, k=3),))


class _AllPair:
    """Policy routing every query to the first pair column."""
    name = "allpair"

    def __init__(self, pairs):
        self.acceptance = AcceptanceTracker(pairs)

    def route(self, batch, rng=None):
        return np.full(batch.n, batch.m - 1, int)   # last column = pair 0


@pytest.mark.slow
def test_routed_dispatch_runs_pairs_and_feeds_acceptance():
    """Full server loop: the scheduler dispatches pair-column assignments
    through admit_spec, spec sequences complete with strong-only-identical
    outputs, verify rounds feed the policy's AcceptanceTracker, and both
    allocators drain."""
    from repro.configs import get_smoke_config
    from repro.serving.engine import (Endpoint, MultiLLMServer, Request,
                                      null_route_features)

    rng = np.random.RandomState(1)
    cfg = get_smoke_config("h2o-danube-3-4b")
    pairs = (SpecPair(0, 1, k=3),)
    eps = [Endpoint(cfg, max_concurrency=2, t_max=64, seed=i, page_size=8,
                    sync_every=4) for i in (7, 0)]
    pol = _AllPair(pairs)
    srv = MultiLLMServer(eps, pol, batch_size=2, spec_pairs=pairs)
    prompts = [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 7, 4)]
    for i, p in enumerate(prompts):
        srv.submit(Request(rid=i, tokens=p, max_new=8))
    done = srv.run(null_route_features)
    assert sorted(r.rid for r in done) == list(range(len(prompts)))
    assert srv.spec_rounds > 0
    assert all(r.endpoint == len(eps) for r in done)    # pair column 0
    assert int(pol.acceptance.rounds[0]) == srv.spec_rounds
    assert srv.spec_emitted == sum(len(r.output) for r in done)

    # strong-only reference on the verify endpoint
    ref_ep = Endpoint(cfg, max_concurrency=2, t_max=64, seed=0, page_size=8,
                      sync_every=4)
    outs = {}
    for i, p in enumerate(prompts):
        r = Request(rid=100 + i, tokens=p, max_new=8)
        ref_ep.admit(r)
        while ref_ep.active_count():
            ref_ep.step()
        outs[i] = r.output
    for r in done:
        assert r.output == outs[r.rid], r.rid
    for ep in eps:
        assert len(ep.alloc.free_slots) == ep.L
        assert len(ep.alloc.free_pages) == ep.alloc.n_pages - 1


# ---------------------------------------------------------------------------
# adaptive window sizing
# ---------------------------------------------------------------------------

def test_adaptive_window_unit():
    aw = AdaptiveWindow(8.0, lo=2.0, hi=16.0, target_iters=50, deep_queue=4)
    # expensive solve -> widen; clamped at hi
    assert aw.update(iters_run=60, queue_depth=0) == 12.0
    assert aw.update(60, 0) == 16.0
    assert aw.update(60, 0) == 16.0          # clamp: no further growth
    assert aw.widened == 2
    # cheap solve with a deep backlog -> narrow; clamped at lo
    for _ in range(8):
        aw.update(iters_run=3, queue_depth=10)
    assert aw.window == 2.0 and aw.narrowed > 0
    # cheap solve with a SHALLOW queue leaves the width alone
    w = aw.update(3, 1)
    assert w == 2.0
    # mid-band solve (neither bound) is a no-op
    assert aw.update(30, 100) == 2.0
    with pytest.raises(ValueError):
        AdaptiveWindow(1.0, lo=2.0, hi=16.0)     # window < lo
    with pytest.raises(ValueError):
        AdaptiveWindow(4.0, grow=0.9)            # grow <= 1


def test_adaptive_window_in_server_loop():
    """MultiLLMServer threads the AdaptiveWindow through StreamController
    into the ControlLoop: a costly policy widens the live window, a cheap
    one with a backlog narrows it."""
    from repro.configs import get_smoke_config
    from repro.serving.engine import (Endpoint, MultiLLMServer, Request,
                                      null_route_features)
    from repro.core.baselines import BalanceAware

    class _Costly(BalanceAware):
        dual_iters = 0

        def route(self, batch, rng=None):
            self.dual_iters += 100       # looks like an expensive solve
            return super().route(batch, rng=rng)

    cfg = get_smoke_config("h2o-danube-3-4b")
    rng = np.random.RandomState(0)

    def _run(policy, aw):
        eps = [Endpoint(cfg, max_concurrency=2, t_max=64, seed=0,
                        page_size=8, sync_every=4)]
        srv = MultiLLMServer(eps, policy, batch_size=1, window_steps=aw.window,
                             adapt_window=aw)
        for i in range(5):
            srv.submit(Request(rid=i, tokens=rng.randint(1, 500, (5,)),
                               max_new=2))
        done = srv.run(null_route_features)
        assert len(done) == 5
        return aw

    aw = _run(_Costly(), AdaptiveWindow(2.0, lo=1.0, hi=32.0,
                                        target_iters=50))
    assert aw.widened > 0 and aw.window > 2.0
    # BalanceAware reports no dual iters; a backlog deeper than 0 narrows
    aw = _run(BalanceAware(), AdaptiveWindow(2.0, lo=0.5, hi=32.0,
                                             target_iters=50, deep_queue=0))
    assert aw.narrowed > 0 and aw.window < 2.0
