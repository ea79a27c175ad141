"""Kernel-backed ECCOS dual solver: same contract as ``core.optimizer``.

``solve_fused`` issues exactly ONE ``pallas_call`` per solve — the whole
dual-ascent loop (all iterations, best-feasible tracking, final emit) runs
inside ``fused_dual_solve``.  The seed implementation launched one kernel per
dual iteration (150 launches per solve); that structure is gone.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.optimizer import SolveInfo, _mode_params, _normalize_problem

from .kernel import fused_dual_solve


@partial(jax.jit,
         static_argnames=("mode", "iters", "bq", "patience", "norm_grad",
                          "interpret"))
def solve_fused(cost, quality, threshold, loads, *, mode: str = "quality",
                iters: int = 150, lr_con: float = 4.0, lr_load: float = 0.5,
                bq: int = 256, lam0=0.0, lam20=None, stall_tol=0.0,
                step0=0.0, patience: int = 3, norm_grad: bool = False,
                interpret=None):
    """Fused-kernel dual solve.  Returns (x (N,), SolveInfo) — the same
    uniform schema as the jit reference (``DualSolver.solve``).  ``lam0`` /
    ``lam20`` warm-start the multipliers for streaming windows, and
    ``stall_tol`` enables the in-kernel freeze early-exit (see
    ``fused_dual_solve``)."""
    n, m = cost.shape
    cost = jnp.asarray(cost, jnp.float32)
    quality = jnp.asarray(quality, jnp.float32)
    loads = jnp.asarray(loads, jnp.float32)
    budget_mode = mode == "budget"
    a_mat, b_mat, t_eff, lr_eff = _mode_params(
        cost, quality, threshold, lr_con, budget_mode=budget_mode)
    # scale-free conditioning — the SAME helper as the reference
    # (core.optimizer._normalize_problem), so fused and reference warm
    # trajectories stay bit-identical; the kernel sees the normalized
    # problem and λ/λ2 convert back to true units at the end
    a_bar = b_bar = jnp.float32(1.0)
    lam0 = jnp.asarray(lam0, jnp.float32)
    if lam20 is None:
        lam20 = jnp.zeros((m,), jnp.float32)
    lam20 = jnp.asarray(lam20, jnp.float32)
    if norm_grad:
        (a_mat, b_mat, t_eff, lr_eff, lr_load, lam0, lam20,
         a_bar, b_bar) = _normalize_problem(
            a_mat, b_mat, t_eff, lr_con, lr_load, lam0, lam20, loads)

    out, vec, nb = fused_dual_solve(
        a_mat, b_mat, t_eff, loads, iters=iters, lr_eff=lr_eff,
        lr_load=lr_load, bq=bq, lam0=lam0, lam20=lam20,
        stall_tol=stall_tol, step0=step0, patience=patience,
        interpret=interpret)
    lam, lam_b, best_obj, found_f, asum, bsum = (
        out[0], out[1], out[2], out[3], out[4], out[5])
    lam2, lam2b, cnt = vec[0], vec[1], vec[2]

    if nb == 1:
        # single-block kernel: every iteration (incl. the last) is finalized
        # and the final dual update applied in-kernel
        lam_fin, lam2_fin = lam, lam2
        lam_best, lam2_best = lam_b, lam2b
        found = found_f > 0.0
        iters_run = out[6].astype(jnp.int32)
    else:
        # finalize the last iteration (the grid kernel finalizes iteration
        # t-1 at the start of iteration t, so iters-1 is finalized here) —
        # unless the solve froze (early exit), in which case the reference
        # while_loop exited before ever seeing this iterate
        active = out[7] < jnp.float32(patience)
        feasible_last = active & (bsum <= t_eff) & jnp.all(cnt <= loads)
        better_last = feasible_last & (asum < best_obj)
        lam_best = jnp.where(better_last, lam, lam_b)
        lam2_best = jnp.where(better_last, lam2, lam2b)
        best_obj = jnp.where(better_last, asum, best_obj)
        found = (found_f > 0.0) | feasible_last
        # ... including the final dual update (step 1/sqrt(step0 + iters))
        step = jax.lax.rsqrt(jnp.asarray(step0, jnp.float32) + iters)
        lam_fin = jnp.where(active, jnp.maximum(
            lam + lr_eff * step * (bsum - t_eff), 0.0), lam)
        lam2_fin = jnp.where(active, jnp.maximum(
            lam2 + lr_load * step * (cnt - loads), 0.0), lam2)
        iters_run = (out[6] + active.astype(jnp.float32)).astype(jnp.int32)

    # emit: argmin is deterministic, so the best-feasible assignment is
    # exactly reproduced from its multipliers (no N-sized kernel state)
    lam_sel = jnp.where(found, lam_best, lam_fin)
    lam2_sel = jnp.where(found, lam2_best, lam2_fin)
    x = jnp.argmin(a_mat + lam_sel * b_mat + lam2_sel[None, :],
                   axis=1).astype(jnp.int32)
    # onehot reductions rather than gathers (gathers are slow on CPU XLA)
    onehot = (x[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (n, m), 1)).astype(jnp.float32)
    asum_e = (a_mat * onehot).sum()
    csum = (cost * onehot).sum()
    qmean = (quality * onehot).sum() / n
    info = SolveInfo(
        lam=lam_fin * a_bar / b_bar, lam_load=lam2_fin * a_bar,
        feasible=found, cost=csum,
        quality=qmean, counts=onehot.sum(axis=0),
        objective=jnp.where(found, best_obj, asum_e) * a_bar,
        iters_run=iters_run,
    )
    return x, info


def solve_assignment_kernel(cost, quality, alpha, loads, *, iters: int = 150,
                            lr_quality: float = 4.0, lr_workload: float = 0.5,
                            bq: int = 256):
    """Legacy quality-mode entry point (one fused launch per solve)."""
    return solve_fused(cost, quality, alpha, loads, mode="quality",
                       iters=iters, lr_con=lr_quality, lr_load=lr_workload,
                       bq=bq)
