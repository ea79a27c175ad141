"""Mesh-sharded dual solver (ISSUE 6): query-axis sharding of the blocked
dual ascent, mask-aware window padding, and the benchmark-runner registry.

Fast tests run in-process on one device (the blocked solve is the same code
path the mesh uses — ``shards > 1`` without a mesh partitions into the same
blocks, so single-device tests pin the exact machinery the 8-device tests
then distribute).  The 8-device tests are subprocesses: XLA's device-count
flag must be set before jax initializes.
"""
import glob
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(snippet: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(snippet)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _instance(n=96, m=5, seed=0):
    rng = np.random.default_rng(seed)
    cost = (rng.uniform(0.2, 3.0, (n, m)) * 1e-3).astype(np.float32)
    quality = rng.uniform(0.0, 1.0, (n, m)).astype(np.float32)
    loads = np.full((m,), float(n) / m + 4, np.float32)
    return cost, quality, loads


# ---------------------------------------------------------------------------
# padding helpers + mask-aware ledger (single device, fast)
# ---------------------------------------------------------------------------

def test_pad_bucket_powers_of_two():
    from repro.core.baselines import pad_bucket
    assert [pad_bucket(k) for k in (1, 2, 3, 5, 64, 65)] == \
        [1, 2, 4, 8, 64, 128]
    # multiple=8: smallest 8*2^k holding n -> every bucket divides by 8
    assert [pad_bucket(k, 8) for k in (1, 8, 9, 37, 64, 65)] == \
        [8, 8, 16, 64, 64, 128]
    for k in (1, 7, 100, 1000):
        assert pad_bucket(k, 8) % 8 == 0 and pad_bucket(k, 8) >= k


def test_pad_batch_rows_inert():
    from repro.core.baselines import RouteBatch, pad_batch
    b = RouteBatch(queries=["a", "b", "c"], input_len=np.arange(3.0),
                   price_in=np.ones(2), price_out=np.ones(2),
                   loads=np.full(2, 4.0), counts=np.zeros(2),
                   cost_true=np.ones((3, 2)), correct_true=np.ones((3, 2)))
    p = pad_batch(b, 8)
    assert p.n == 8 and p.queries[3:] == [""] * 5
    assert np.all(p.input_len[3:] == 0) and np.all(p.cost_true[3:] == 0)
    assert pad_batch(b, 3) is b          # no-op when already large enough


def test_blocked_pad_content_cannot_leak():
    """The blocked solve zeroes padded cost/quality rows, so garbage pad
    content must be bit-indistinguishable from zero pad content — in the
    assignment, the SolveInfo, and the streaming ledger."""
    from repro.core.optimizer import DualSolver, init_dual_state
    cost, quality, loads = _instance(n=64, m=5)
    n_pad = 96                       # 96/4 shards -> 24-row blocks
    rng = np.random.default_rng(9)
    s = DualSolver(mode="quality", iters=40, lr_constraint=4.0,
                   norm_grad=True, shards=4)
    outs = []
    for fill in (0.0, None):         # zero pads vs garbage pads
        cp = np.zeros((n_pad, 5), np.float32)
        qp = np.zeros((n_pad, 5), np.float32)
        if fill is None:
            cp[64:] = rng.uniform(10, 20, (32, 5))
            qp[64:] = rng.uniform(0, 1, (32, 5))
        cp[:64], qp[:64] = cost, quality
        x, info, st = s.route_window(cp, qp, 0.55, loads,
                                     init_dual_state(5), n_valid=64)
        outs.append((np.asarray(x), info, st))
    (xa, ia, sa), (xb, ib, sb) = outs
    assert np.array_equal(xa[:64], xb[:64])
    for f in ("lam", "lam_load", "budget_spent", "sr_deficit", "steps"):
        assert np.array_equal(np.asarray(getattr(sa, f)),
                              np.asarray(getattr(sb, f))), f
    # the ledger counts ONLY valid rows
    assert float(np.asarray(ia.counts).sum()) == 64
    chosen_cost = np.float32(cost[np.arange(64), xa[:64]].sum())
    assert np.isclose(float(sa.budget_spent), float(chosen_cost), rtol=1e-5)
    # capacity respected on the valid rows
    cnt = np.bincount(xa[:64], minlength=5)
    assert np.all(cnt <= loads)


def test_blocked_solve_agrees_with_legacy():
    """shards>1 without a mesh runs the same blocked path the mesh
    distributes; it must agree with the legacy monolithic solve on the
    things that matter (feasibility, realized cost/quality — assignments
    can differ on numerical ties)."""
    from repro.core.optimizer import DualSolver
    cost, quality, loads = _instance(n=96, m=5)
    for mode, thr, lr in (("quality", 0.55, 4.0), ("budget", 0.08, 50.0)):
        ref = DualSolver(mode=mode, iters=60, lr_constraint=lr,
                         norm_grad=True)
        blk = DualSolver(mode=mode, iters=60, lr_constraint=lr,
                         norm_grad=True, shards=4)
        x0, i0 = ref.solve(cost, quality, thr, loads)
        x1, i1 = blk.solve(cost, quality, thr, loads)
        x0, x1 = np.asarray(x0), np.asarray(x1)
        assert np.all(np.bincount(x1, minlength=5) <= loads)
        mismatch = float(np.mean(x0 != x1))
        assert mismatch <= 0.15, (mode, mismatch)
        q0 = quality[np.arange(96), x0].mean()
        q1 = quality[np.arange(96), x1].mean()
        c0 = cost[np.arange(96), x0].sum()
        c1 = cost[np.arange(96), x1].sum()
        assert abs(q1 - q0) < 0.05, (mode, q0, q1)
        assert abs(c1 - c0) / max(c0, 1e-9) < 0.2, (mode, c0, c1)


def test_solver_rejects_nondivisible_shards():
    from repro.core.optimizer import DualSolver
    cost, quality, loads = _instance(n=90, m=5)     # 90 % 4 != 0
    s = DualSolver(mode="quality", iters=10, shards=4, norm_grad=True)
    with pytest.raises(ValueError, match="divide"):
        s.solve(cost, quality, 0.5, loads)


# ---------------------------------------------------------------------------
# benchmark registry guard (satellite: CI/tooling)
# ---------------------------------------------------------------------------

def test_bench_runner_enumerates_every_benchmark():
    """Every ``benchmarks/bench_*.py`` must be registered in ``run.py`` —
    a bench that exists but never runs silently rots."""
    bench_dir = os.path.join(_ROOT, "benchmarks")
    on_disk = {os.path.splitext(os.path.basename(p))[0]
               for p in glob.glob(os.path.join(bench_dir, "bench_*.py"))}
    with open(os.path.join(bench_dir, "run.py")) as f:
        registered = set(re.findall(r'"benchmarks\.(bench_\w+)"', f.read()))
    assert on_disk == registered, (
        f"unregistered: {sorted(on_disk - registered)}, "
        f"stale: {sorted(registered - on_disk)}")


# ---------------------------------------------------------------------------
# 8-device parity (subprocess; heavy compiles)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_solver_bit_parity_8dev():
    """The tentpole contract: the mesh-sharded solve is BIT-identical to the
    single-device blocked solve — cold (every SolveInfo field), warm across
    a 3-window stream (every DualState ledger field), and the stall early
    exit fires after the identical iteration."""
    print(_run("""
        import numpy as np, jax
        assert jax.device_count() == 8, jax.devices()
        from repro.common import use_mesh, query_mesh, query_rules
        from repro.core.optimizer import DualSolver, init_dual_state

        rng = np.random.default_rng(1)
        n, m = 1024, 6
        cost = (rng.uniform(0.2, 3.0, (n, m)) * 1e-3).astype(np.float32)
        quality = rng.uniform(0.0, 1.0, (n, m)).astype(np.float32)
        loads = np.full((m,), 256.0, np.float32)
        mesh, rules = query_mesh(8), query_rules()
        bit_eq = lambda a, b: np.array_equal(np.asarray(a), np.asarray(b))

        for mode, thr in (("quality", 0.55), ("budget", 0.3)):
            lr = 4.0 if mode == "quality" else 50.0
            for use_kernel in (False, True):
                s = DualSolver(mode=mode, iters=60, lr_constraint=lr,
                               stall_tol=1e-4, norm_grad=True, shards=8,
                               use_kernel=use_kernel)
                x0, i0 = s.solve(cost, quality, thr, loads)
                with use_mesh(mesh, rules):
                    x1, i1 = s.solve(cost, quality, thr, loads)
                assert bit_eq(x0, x1), (mode, use_kernel, "cold assign")
                for f in ("lam", "lam_load", "feasible", "iters_run",
                          "counts", "cost", "quality", "objective"):
                    assert bit_eq(getattr(i0, f), getattr(i1, f)), \\
                        (mode, use_kernel, f)
                st_a = st_b = init_dual_state(m)
                for w in range(3):
                    cw = (rng.uniform(0.2, 3.0, (n, m)) * 1e-3
                          ).astype(np.float32)
                    qw = rng.uniform(0.0, 1.0, (n, m)).astype(np.float32)
                    xa, ia, st_a = s.route_window(cw, qw, thr, loads, st_a,
                                                  share=1 / (3 - w))
                    with use_mesh(mesh, rules):
                        xb, ib, st_b = s.route_window(cw, qw, thr, loads,
                                                      st_b, share=1 / (3 - w))
                    assert bit_eq(xa, xb), (mode, use_kernel, "window", w)
                    for f in ("lam", "lam_load", "budget_spent",
                              "sr_deficit", "steps"):
                        assert bit_eq(getattr(st_a, f), getattr(st_b, f)), \\
                            (mode, use_kernel, f, w)
                s2 = DualSolver(mode=mode, iters=200, lr_constraint=lr,
                                stall_tol=0.5, stall_patience=2,
                                norm_grad=True, shards=8,
                                use_kernel=use_kernel)
                _, j0 = s2.solve(cost, quality, thr, loads)
                with use_mesh(mesh, rules):
                    _, j1 = s2.solve(cost, quality, thr, loads)
                assert bit_eq(j0.iters_run, j1.iters_run)
                if mode == "quality":
                    assert float(j0.iters_run) < 200   # early exit fires
                print(mode, use_kernel, "bit-exact")
        print("MESH PARITY OK")
    """))


@pytest.mark.slow
def test_sharded_route_window_stream_parity_8dev():
    """End-to-end predict->solve under the mesh: non-divisible windows
    (37/53/30) pad to shard-divisible buckets, assignments are bit-equal to
    the single-device stream, and the ledger matches to float tolerance.
    The predictor's matmuls retile across local batch sizes, so predicted
    quality — and with it the quality ledger and λ — may drift by a few
    ulps; the dual-step count and the budget ledger stay exact."""
    print(_run("""
        import numpy as np, jax
        assert jax.device_count() == 8
        from repro.common import use_mesh, query_mesh, query_rules
        from repro.data.qaserve import generate
        from repro.core.router import OmniRouter, RouterConfig
        from repro.core.hybrid import HybridPredictor, HybridConfig
        from repro.core.predictor import PredictorConfig
        from repro.core.control import StreamController

        ds = generate(n=300, seed=0)
        tr, va, te = ds.split(0.5, 0.0)
        pred = HybridPredictor(PredictorConfig(n_models=ds.m),
                               HybridConfig()).fit(tr, steps=40)
        loads = np.full(ds.m, 50.0)
        counts = np.zeros(ds.m)
        windows = ((0, 37), (37, 53), (90, 30))

        def run(meshed):
            r = OmniRouter(pred, RouterConfig(alpha=0.6, iters=60, shards=8))
            ctrl = StreamController(r, horizon=te.n)
            xs = []
            ctxs = (use_mesh(query_mesh(8), query_rules()),) if meshed else ()
            if meshed:
                with ctxs[0]:
                    assert r.window_multiple() == 8   # buckets divide evenly
                    for i0, sz in windows:
                        xs.append(ctrl.route(
                            te.subset(np.arange(i0, i0 + sz)),
                            loads, counts))
            else:
                for i0, sz in windows:
                    xs.append(ctrl.route(te.subset(np.arange(i0, i0 + sz)),
                                         loads, counts))
            return xs, ctrl.state

        x_m, st_m = run(True)
        x_s, st_s = run(False)
        for (i0, sz), a, b in zip(windows, x_m, x_s):
            assert len(a) == sz                       # padding sliced off
            assert np.array_equal(a, b), (i0, sz)
        for f in ("budget_spent", "steps"):
            assert np.array_equal(np.asarray(getattr(st_m, f)),
                                  np.asarray(getattr(st_s, f))), f
        assert np.allclose(np.asarray(st_m.sr_deficit),
                           np.asarray(st_s.sr_deficit), rtol=1e-5, atol=0)
        for f in ("lam", "lam_load"):
            assert np.allclose(np.asarray(getattr(st_m, f)),
                               np.asarray(getattr(st_s, f)),
                               rtol=1e-4, atol=1e-5), f
        print("MESH ROUTER OK")
    """))
