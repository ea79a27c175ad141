"""ECCOS/OmniRouter constrained optimizer (paper §3.2, Appendix A).

Primal (quality mode):
    min_x  Σ c_ij x_ij
    s.t.   (1/N) Σ a_ij x_ij >= alpha        (quality)
           Σ_i x_ij <= L_j                    (per-model workload)
           Σ_j x_ij = 1,  x in {0,1}

Budget mode (OmniRouter title):  max quality s.t. Σ cost <= B — the *same*
machinery with the roles of cost/quality swapped.  Both modes are one code
path: with the unified parameterization

    scores_ij = A_ij + lam * B_ij + lam2_j,   feasible  ⇔  Σ B[i, x_i] <= t

quality mode sets (A, B, t) = (cost, -quality/N, -alpha) and budget mode sets
(A, B, t) = (-quality, cost, B).  Dual subgradient ascent (Eq. 9-12) tracks
the scalar constraint multiplier `lam` and per-model workload multipliers
`lam2`; we keep the **best feasible iterate** (min Σ A among feasible x) —
dual iterates oscillate around the constraint boundary and the serving loop
wants a concrete feasible pick.

The post-solve feasibility pass (`repair_workload` + `primal_polish`) is
vectorized JAX — jit-compiled ``lax.while_loop``s with no Python-level
per-query loops, so the whole route() pipeline stays on device.  NumPy
reference implementations live in ``repro.kernels.lagrangian_assign.ref`` as
test oracles.

Streaming (ISSUE 5): the solver is no longer one-shot only.  A
:class:`DualState` carries the multipliers and the cumulative constraint
ledger (budget spent, realized-quality deficit) across arrival windows;
``route_window`` folds the ledger into each window's *effective* threshold
(remaining budget × horizon share in budget mode, α corrected by the
accumulated deficit in quality mode), warm-starts the dual ascent from the
previous window's multipliers, and returns the updated state.  Warm-started
windows sit near the dual optimum, so the ascent stalls almost immediately —
``stall_tol`` turns that into an early exit and ``SolveInfo.iters_run``
records how many iterations actually ran.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis import sanitize as _sanitize


class SolveInfo(NamedTuple):
    """Uniform solver diagnostics — identical schema in both modes."""

    lam: jax.Array        # scalar constraint multiplier (λ1 / µ)
    lam_load: jax.Array   # (M,) per-model workload multipliers λ2
    feasible: jax.Array   # bool — some iterate satisfied all constraints
    cost: jax.Array       # Σ predicted $ of the returned assignment
    quality: jax.Array    # mean predicted quality of the returned assignment
    counts: jax.Array     # (M,) per-model counts of the returned assignment
    objective: jax.Array  # mode objective of returned x (cost | -Σ quality)
    iters_run: jax.Array  # int32 — dual iterations actually run (early exit)


class DualState(NamedTuple):
    """Streaming dual-controller state carried across arrival windows.

    A plain pytree of arrays, so it round-trips through ``jax.jit``
    unchanged: window k+1's solve starts from window k's multipliers, and
    the scalar ledger tracks the *cumulative* constraint position of the
    whole stream (not re-derived per batch).
    """

    lam: jax.Array           # () carried constraint multiplier (λ1 / µ)
    lam_load: jax.Array      # (M,) carried workload multipliers λ2
    budget_spent: jax.Array  # () cumulative $ routed so far (both modes)
    sr_deficit: jax.Array    # () cumulative Σ(α − q_chosen); >0 ⇒ behind α
    steps: jax.Array         # () cumulative dual iterations on this stream —
    #                          continues the 1/√t step schedule across
    #                          windows (restarting it at 1 would kick the
    #                          warm multipliers away from the optimum and
    #                          forfeit the warm-start iteration savings)


def init_dual_state(m: int) -> DualState:
    """Fresh stream state: zero multipliers, empty ledger."""
    return DualState(lam=jnp.zeros(()), lam_load=jnp.zeros((m,)),
                     budget_spent=jnp.zeros(()), sr_deficit=jnp.zeros(()),
                     steps=jnp.zeros(()))


def fold_threshold(mode: str, threshold, state: Optional[DualState], n: int,
                   share=1.0):
    """This window's *effective* threshold given the stream ledger.

    Budget mode: spend ``share`` of the remaining global budget (share is
    the window's fraction of the remaining horizon, so a stationary stream
    spreads the budget evenly and any under-spend rolls forward).  Quality
    mode: raise/lower α by the realized per-query deficit so the stream's
    cumulative mean — not each window in isolation — meets the constraint.
    """
    threshold = jnp.asarray(threshold, jnp.float32)
    if state is None:
        return threshold
    if mode == "budget":
        remaining = jnp.maximum(threshold - state.budget_spent, 0.0)
        return remaining * jnp.asarray(share, jnp.float32)
    return jnp.clip(threshold + state.sr_deficit / n, 0.0, 1.0)


def _mode_params(cost, quality, threshold, lr_con, *, budget_mode: bool,
                 n_eff=None):
    """Map (cost, quality, threshold) onto the unified (A, B, t, lr).

    ``n_eff`` overrides the static row count in quality mode's 1/N scaling —
    a mask-padded window normalizes by its VALID rows, not its padded shape
    (padding rows carry zeros and must not dilute the window mean)."""
    n = cost.shape[0] if n_eff is None else n_eff
    if budget_mode:
        return -quality, cost, threshold, lr_con
    return cost, -quality / n, -threshold, lr_con * n


def _normalize_problem(a_mat, b_mat, t_eff, lr_con, lr_load, lam0, lam20,
                       loads):
    """Scale-free conditioning shared by the jnp reference and the fused
    kernel wrapper (they MUST stay bit-identical — warm-parity tests assert
    fused == reference exactly): both unified matrices are normalized to
    unit mean magnitude, the λ step becomes lr·(relative residual), the λ2
    step is conditioned on the loads scale, and the warm-start multipliers
    convert into normalized units (λ̂ = λ·b̄/ā, λ̂2 = λ2/ā).  Returns the
    normalized problem plus (ā, b̄) for converting the emitted multipliers
    back to true units.
    """
    a_bar = jnp.mean(jnp.abs(a_mat)) + jnp.float32(1e-30)
    b_bar = jnp.mean(jnp.abs(b_mat)) + jnp.float32(1e-30)
    a_mat = a_mat / a_bar
    b_mat = b_mat / b_bar
    t_eff = t_eff / b_bar
    lr_eff = lr_con / (1.0 + jnp.abs(t_eff))
    lr_load_eff = lr_load / (1.0 + jnp.mean(loads))
    lam0 = lam0 * b_bar / a_bar
    lam20 = lam20 / a_bar
    return a_mat, b_mat, t_eff, lr_eff, lr_load_eff, lam0, lam20, a_bar, b_bar


def _chosen_sum(mat, x):
    return jnp.take_along_axis(mat, x[:, None], axis=1).sum()


@partial(jax.jit, static_argnames=("mode", "iters", "patience", "norm_grad"))
def _solve_ref(cost, quality, threshold, loads, lam0=0.0, lam20=None,
               stall_tol=0.0, step0=0.0, *, mode: str, iters: int,
               lr_con: float, lr_load: float, patience: int = 3,
               norm_grad: bool = False):
    """jnp reference dual ascent — the oracle for the fused Pallas path.

    ``lam0``/``lam20`` warm-start the multipliers (a streaming window starts
    from the previous window's dual point) and ``step0`` continues the
    diminishing step schedule where the stream left off (1/√(1+step0+t)).
    When ``stall_tol`` > 0 the while_loop exits once a feasible iterate is
    banked and ``patience`` iterations (cumulative) have either stalled the
    multipliers or sat on the constraint boundary — warm-started windows bank
    most of their wall-clock here.  ``stall_tol=0`` with ``step0=0``
    reproduces the fixed-``iters`` trajectory exactly.
    """
    n, m = cost.shape
    cost = cost.astype(jnp.float32)
    quality = quality.astype(jnp.float32)
    loads = loads.astype(jnp.float32)
    stall_tol = jnp.asarray(stall_tol, jnp.float32)
    step0 = jnp.asarray(step0, jnp.float32)
    a_mat, b_mat, t_eff, lr_eff = _mode_params(
        cost, quality, threshold, lr_con, budget_mode=(mode == "budget"))
    # norm_grad: scale-free conditioning — BOTH unified matrices are
    # normalized to unit mean magnitude and the step uses the residual
    # relative to the threshold, so one O(1) lr works across window sizes,
    # modes and $ scales.  Raw units otherwise put the dual optimum at
    # λ* ~ Ā-scale/B̄-scale (1e4 when one side is $/query ~1e-4) while the
    # subgradient is in sum units, so the ascent either limit-cycles or
    # never arrives.  Streaming opts in; the legacy one-shot trajectory is
    # untouched by default.  The emitted λ is converted back to true units
    # (λ = λ̂·ā/b̄) for repair and DualState.
    a_bar = b_bar = jnp.float32(1.0)
    lam0 = jnp.asarray(lam0, jnp.float32)
    lam20 = jnp.zeros((m,)) if lam20 is None else jnp.asarray(lam20)
    lam20 = lam20.astype(jnp.float32).reshape((m,))
    lr_load_eff = lr_load
    if norm_grad:
        (a_mat, b_mat, t_eff, lr_eff, lr_load_eff, lam0, lam20,
         a_bar, b_bar) = _normalize_problem(
            a_mat, b_mat, t_eff, lr_con, lr_load, lam0, lam20, loads)

    def assign(lam, lam2):
        scores = a_mat + lam * b_mat + lam2[None, :]
        return jnp.argmin(scores, axis=1).astype(jnp.int32)

    def cond(carry):
        t, _, _, _, _, _, stall = carry
        return (t < iters) & (stall < patience)

    def body(carry):
        t, lam, lam2, best_a, best_x, found = carry[:6]
        x = assign(lam, lam2)
        asum = _chosen_sum(a_mat, x)
        bsum = _chosen_sum(b_mat, x)
        cnt = jnp.zeros((m,), jnp.float32).at[x].add(1.0)
        feasible = (bsum <= t_eff) & jnp.all(cnt <= loads)
        better = feasible & (asum < best_a)
        best_a = jnp.where(better, asum, best_a)
        best_x = jnp.where(better, x, best_x)
        found = found | feasible
        # diminishing steps for subgradient convergence
        step = 1.0 / jnp.sqrt(1.0 + step0 + t.astype(jnp.float32))
        lam_new = jnp.maximum(lam + lr_eff * step * (bsum - t_eff), 0.0)
        lam2_new = jnp.maximum(
            lam2 + lr_load_eff * step * (cnt - loads), 0.0)
        # stall signal: the multipliers stopped moving (relative), OR the
        # iterate sits on the constraint boundary (small relative residual)
        # — either way further ascent has nothing left to gain
        delta = jnp.abs(lam_new - lam) + jnp.abs(lam2_new - lam2).sum()
        denom = 1.0 + jnp.abs(lam_new) + jnp.abs(lam2_new).sum()
        resid = jnp.abs(bsum - t_eff) / (1.0 + jnp.abs(t_eff))
        stalled = found & ((delta < stall_tol * denom)
                           | (resid < stall_tol))
        # cumulative (not consecutive) count: an oscillating dual only
        # touches the boundary once per cycle, so a reset would never let
        # the counter reach `patience`
        stall = carry[6] + stalled.astype(jnp.int32)
        return t + 1, lam_new, lam2_new, best_a, best_x, found, stall

    init = (jnp.asarray(0, jnp.int32),
            jnp.asarray(lam0, jnp.float32).reshape(()),
            lam20,
            jnp.asarray(jnp.inf), jnp.zeros((n,), jnp.int32),
            jnp.asarray(False), jnp.asarray(0, jnp.int32))
    t_run, lam, lam2, best_a, best_x, found, _ = jax.lax.while_loop(
        cond, body, init)
    x_last = assign(lam, lam2)
    x = jnp.where(found, best_x, x_last)
    info = SolveInfo(
        lam=lam * a_bar / b_bar, lam_load=lam2 * a_bar, feasible=found,
        cost=_chosen_sum(cost, x), quality=jnp.take_along_axis(
            quality, x[:, None], axis=1).sum() / n,
        counts=jnp.zeros((m,), jnp.float32).at[x].add(1.0),
        objective=jnp.where(found, best_a,
                            _chosen_sum(a_mat, x_last)) * a_bar,
        iters_run=t_run,
    )
    return x, info


# ---------------------------------------------------------------------------
# Mesh-sharded / blocked window solve (ISSUE 6).
#
# The only cross-query coupling in the dual ascent is the per-iteration
# reduction [ΣA, ΣB, histogram].  ``shards`` turns that reduction into a
# BLOCKED one: the (N, M) problem is viewed as (S, N/S, M), each shard
# produces its contiguous partial sums, and the partials combine through one
# ordered (S,)-array sum.  Under an active mesh whose rules map the logical
# "query" axis to real devices, the identical program runs through
# ``shard_map``: each device computes its local shard partials, an ordered
# ``all_gather`` (a psum with a deterministic combine order) collects the
# (S,) partial vector, and every device applies the same local sum — so the
# multipliers (λ, λ2) stay replicated, every device walks the identical
# ascent trajectory, and the sharded solve is BIT-IDENTICAL to the blocked
# single-device solve.  (Every per-block partial is produced by a lax.map
# body of fixed (N/S, M) shape so XLA cannot pick an lblocks-dependent
# summation order — see ``bmap`` below.)  Repair/polish run shard-locally
# (lax.map over local shards on one device == one shard per device under
# shard_map) against an exact integer partition of the capacity vector, so
# no collective is needed inside their while_loops.
#
# The same path carries the mask-aware window padding: ``n_valid`` marks the
# valid-row prefix of a padded window; padding rows are zeroed out of every
# matrix, masked out of every histogram, excluded from repair/polish moves,
# and therefore never touch the quality/budget ledger.
# ---------------------------------------------------------------------------

def _shard_quotas(loads, shard_ids, gshards: int):
    """Exact integer partition of per-model capacity across query shards:
    quota_j(s) = floor(L_j·(s+1)/S) − floor(L_j·s/S).  Sums to floor(L_j)
    over shards, is deterministic, and evaluates identically whether all
    shards are computed on one device or one shard per device."""
    s = shard_ids.astype(jnp.float32)[:, None]
    g = jnp.float32(gshards)
    hi = jnp.floor(loads[None, :] * ((s + 1.0) / g))
    lo = jnp.floor(loads[None, :] * (s / g))
    return jnp.where(jnp.isfinite(loads)[None, :], hi - lo, loads[None, :])


def _blocked_window_core(a_mat, b_mat, cost, quality, t_eff, p_eff, loads,
                         lr_eff, lr_load_eff, lam0, lam20, stall_tol, step0,
                         n_valid, *, mode: str, iters: int,
                         patience: int, lblocks: int, gshards: int,
                         axis_name, use_stats_kernel: bool, bq: int,
                         polish: bool, norm_grad: bool, lr_con: float,
                         lr_load: float):
    """Dual ascent (+ optional repair/polish + ledger sums) over ``lblocks``
    local query shards.  Runs as-is on one device (lblocks == gshards) and
    inside ``shard_map`` (lblocks == gshards / n_devices, ``axis_name`` set);
    both paths produce bit-identical trajectories — see the block comment
    above.  Returns (x_local, SolveInfo, final csum, final qsum)."""
    nloc, m = a_mat.shape
    nl = nloc // lblocks
    d0 = 0 if axis_name is None else jax.lax.axis_index(axis_name) * lblocks
    shard_ids = d0 + jnp.arange(lblocks)
    # per-shard valid-row counts: padding is always a suffix of the GLOBAL
    # window, so shard s owns rows [s·nl, (s+1)·nl) and clips against it
    nv_loc = jnp.clip(n_valid - shard_ids.astype(jnp.float32) * nl, 0.0, nl)
    a3 = a_mat.reshape(lblocks, nl, m)
    b3 = b_mat.reshape(lblocks, nl, m)
    c3 = cost.reshape(lblocks, nl, m)
    q3 = quality.reshape(lblocks, nl, m)
    nv_loc_i = nv_loc.astype(jnp.int32)
    cols2 = jax.lax.broadcasted_iota(jnp.int32, (nl, m), 1)
    rows2 = jax.lax.broadcasted_iota(jnp.int32, (nl, m), 0)

    def gather(part):
        # deterministic-order psum: device partials concatenate in global
        # shard order, then every device applies the same ordered local sum
        # — the op sequence the blocked single-device path runs verbatim
        if axis_name is None:
            return part
        return jax.lax.all_gather(part, axis_name, tiled=True)

    def bmap(f, *arrs):
        # Per-block partials MUST come from a traced body whose shape is the
        # same (nl, m) on every path — a direct `.sum(axis=(1, 2))` over the
        # (lblocks, ...) stack lets XLA pick a summation order that depends
        # on lblocks (and fuse it with the cross-block combine), which
        # breaks mesh/meshless bit-parity at the ~1e-6 level.  lax.map is a
        # hard loop boundary: the block body compiles once, identically,
        # and the cross-block combine always sees materialized partials.
        return jax.lax.map(lambda t: f(*t), arrs)

    def block_onehot(x1, nv_s):
        return ((x1[:, None] == cols2) & (rows2 < nv_s)).astype(jnp.float32)

    def chosen(mat3, x2):
        part = bmap(lambda mat2, x1, nv_s:
                    (mat2 * block_onehot(x1, nv_s)).sum(),
                    mat3, x2, nv_loc_i)
        return gather(part).sum()

    # Scale-free conditioning (the _normalize_problem convention) computed
    # HERE, with the blocked gather, rather than outside the shard_map: a
    # global jnp.sum outside would hand the reduction to the SPMD
    # partitioner, whose device-split summation order differs from the
    # single-device one — the ~1e-6 λ drift that breaks bit-parity.
    a_bar = b_bar = jnp.float32(1.0)
    if norm_grad:
        denom = n_valid * jnp.float32(m) + jnp.float32(1e-30)
        a_bar = gather(bmap(lambda a2: jnp.abs(a2).sum(), a3)).sum() \
            / denom + jnp.float32(1e-30)
        b_bar = gather(bmap(lambda b2: jnp.abs(b2).sum(), b3)).sum() \
            / denom + jnp.float32(1e-30)
        a_mat, b_mat = a_mat / a_bar, b_mat / b_bar
        a3, b3 = a3 / a_bar, b3 / b_bar
        t_eff = t_eff / b_bar
        lr_eff = jnp.float32(lr_con) / (1.0 + jnp.abs(t_eff))
        lr_load_eff = jnp.float32(lr_load) / (1.0 + jnp.mean(loads))
        lam0 = lam0 * b_bar / a_bar
        lam20 = lam20 / a_bar

    def assign(lam, lam2):
        scores = a3 + lam * b3 + lam2[None, None, :]
        return jnp.argmin(scores, axis=2).astype(jnp.int32)

    if use_stats_kernel:
        from repro.kernels.lagrangian_assign.kernel import shard_stats

        def stats(lam, lam2):
            part = shard_stats(a_mat, b_mat, lam, lam2, nv_loc,
                               lblocks=lblocks, bq=bq)
            tot = gather(part).sum(axis=0)
            return tot[0], tot[1], tot[2:]
    else:
        def stats(lam, lam2):
            def one(a2, b2, nv_s):
                scores = a2 + lam * b2 + lam2[None, :]
                oh = block_onehot(
                    jnp.argmin(scores, axis=1).astype(jnp.int32), nv_s)
                return (a2 * oh).sum(), (b2 * oh).sum(), oh.sum(axis=0)
            pa, pb, pc = bmap(one, a3, b3, nv_loc_i)
            return gather(pa).sum(), gather(pb).sum(), gather(pc).sum(axis=0)

    # no N-sized state crosses an iteration (the fused-kernel discipline):
    # the loop banks the best-feasible iterate's MULTIPLIERS and the caller
    # replays its assignment — argmin is deterministic
    def cond(carry):
        t = carry[0]
        stall = carry[7]
        return (t < iters) & (stall < patience)

    def body(carry):
        t, lam, lam2, best_a, lam_b, lam2_b, found, stall = carry
        asum, bsum, cnt = stats(lam, lam2)
        feasible = (bsum <= t_eff) & jnp.all(cnt <= loads)
        better = feasible & (asum < best_a)
        best_a = jnp.where(better, asum, best_a)
        lam_b = jnp.where(better, lam, lam_b)
        lam2_b = jnp.where(better, lam2, lam2_b)
        found = found | feasible
        step = 1.0 / jnp.sqrt(1.0 + step0 + t.astype(jnp.float32))
        lam_new = jnp.maximum(lam + lr_eff * step * (bsum - t_eff), 0.0)
        lam2_new = jnp.maximum(
            lam2 + lr_load_eff * step * (cnt - loads), 0.0)
        delta = jnp.abs(lam_new - lam) + jnp.abs(lam2_new - lam2).sum()
        denom = 1.0 + jnp.abs(lam_new) + jnp.abs(lam2_new).sum()
        resid = jnp.abs(bsum - t_eff) / (1.0 + jnp.abs(t_eff))
        stalled = found & ((delta < stall_tol * denom)
                           | (resid < stall_tol))
        stall = stall + stalled.astype(jnp.int32)   # cumulative — see _solve_ref
        return t + 1, lam_new, lam2_new, best_a, lam_b, lam2_b, found, stall

    init = (jnp.asarray(0, jnp.int32),
            jnp.asarray(lam0, jnp.float32).reshape(()),
            jnp.asarray(lam20, jnp.float32).reshape((m,)),
            jnp.asarray(jnp.inf), jnp.zeros(()), jnp.zeros((m,)),
            jnp.asarray(False), jnp.asarray(0, jnp.int32))
    (t_run, lam, lam2, best_a, lam_b, lam2_b, found, _
     ) = jax.lax.while_loop(cond, body, init)

    lam_sel = jnp.where(found, lam_b, lam)
    lam2_sel = jnp.where(found, lam2_b, lam2)
    x2 = assign(lam_sel, lam2_sel)
    asum_e = chosen(a3, x2)
    counts = gather(bmap(lambda x1, nv_s: block_onehot(x1, nv_s).sum(axis=0),
                         x2, nv_loc_i)).sum(axis=0)
    info = SolveInfo(
        lam=lam * a_bar / b_bar, lam_load=lam2 * a_bar, feasible=found,
        cost=chosen(c3, x2),
        quality=chosen(q3, x2) / jnp.maximum(n_valid, 1.0),
        counts=counts,
        objective=jnp.where(found, best_a, asum_e) * a_bar,
        iters_run=t_run)

    if polish:
        quotas = _shard_quotas(loads, shard_ids, gshards)
        lam1 = (lam * a_bar / b_bar if mode == "quality"
                else jnp.zeros(()))
        # shard-local repair/polish through the same lax.map boundary (a
        # vmap over the block axis would re-batch their inner reductions
        # with lblocks-dependent shapes — same bit-parity hazard as stats)
        shares = p_eff * nv_loc / jnp.maximum(n_valid, 1.0)

        def one_polish(x1, c2, q2, quota, nv_s, share_s):
            x1 = repair_workload(x1, c2, q2, quota, lam1, nv_s)
            if mode == "quality":
                return primal_polish(x1, c2, q2, p_eff, quota, nv_s)
            # each shard polishes toward its valid-row share of the budget
            return budget_polish(x1, c2, q2, share_s, quota, nv_s)

        x2 = jax.lax.map(lambda t: one_polish(*t),
                         (x2, c3, q3, quotas, nv_loc, shares))
    csum = chosen(c3, x2)
    qsum = chosen(q3, x2)
    return x2.reshape(nloc), info, csum, qsum


@lru_cache(maxsize=None)
def _blocked_window_fn(mesh, axes, *, mode: str, iters: int, lr_con: float,
                       lr_load: float, patience: int, norm_grad: bool,
                       gshards: int, use_stats_kernel: bool, bq: int,
                       polish: bool):
    """Build (and cache per (mesh, statics)) the jitted blocked/sharded
    window solve.  ``mesh``/``axes`` of None compiles the single-device
    blocked program; otherwise the core runs under ``shard_map`` with the
    query axis split over ``axes`` (single-pod ('data',) or multi-pod
    ('pod','data') — straight from the sharding rules)."""
    budget_mode = mode == "budget"
    axis_name = None
    lblocks = gshards
    if mesh is not None:
        axis_name = axes if len(axes) > 1 else axes[0]
        ndev = 1
        for a in axes:
            ndev *= mesh.shape[a]
        lblocks = gshards // ndev
    core = partial(_blocked_window_core, mode=mode, iters=iters,
                   patience=patience, lblocks=lblocks, gshards=gshards,
                   axis_name=axis_name, use_stats_kernel=use_stats_kernel,
                   bq=bq, polish=polish, norm_grad=norm_grad,
                   lr_con=lr_con, lr_load=lr_load)

    def fn(cost, quality, threshold, loads, lam0, lam20, stall_tol, step0,
           n_valid, p_eff):
        n, m = cost.shape
        cost = jnp.asarray(cost, jnp.float32)
        quality = jnp.asarray(quality, jnp.float32)
        loads = jnp.asarray(loads, jnp.float32)
        nvf = jnp.asarray(n_valid, jnp.float32)
        # padding rows (always a suffix) are zeroed so they contribute
        # exactly 0.0 to every reduction — including the stream ledger
        validr = (jnp.arange(n) < nvf)[:, None]
        cost = cost * validr
        quality = quality * validr
        a_mat, b_mat, t_eff, lr_eff = _mode_params(
            cost, quality, jnp.asarray(threshold, jnp.float32), lr_con,
            budget_mode=budget_mode, n_eff=nvf)
        lam0 = jnp.asarray(lam0, jnp.float32)
        lam20 = jnp.asarray(lam20, jnp.float32).reshape((m,))
        lr_load_eff = jnp.asarray(lr_load, jnp.float32)
        # norm_grad conditioning happens INSIDE the core (blocked gather) so
        # its reductions are bit-identical with and without the mesh
        args = (a_mat, b_mat, cost, quality, t_eff,
                jnp.asarray(p_eff, jnp.float32), loads, lr_eff, lr_load_eff,
                lam0, lam20, jnp.asarray(stall_tol, jnp.float32),
                jnp.asarray(step0, jnp.float32), nvf)
        if mesh is None:
            return core(*args)
        qspec = P(axes if len(axes) > 1 else axes[0])
        rep = P()
        sharded = jax.shard_map(
            core, mesh=mesh,
            in_specs=(qspec, qspec, qspec, qspec) + (rep,) * 10,
            out_specs=(qspec, SolveInfo(*([rep] * 8)), rep, rep),
            # the while_loop's gathered reductions keep (λ, λ2) replicated
            # by construction; the static replication checker can't see
            # through the loop, so it is disabled rather than appeased
            check_vma=False)
        return sharded(*args)

    return jax.jit(fn)


@dataclasses.dataclass(frozen=True)
class DualSolver:
    """One device-resident dual solver for both routing modes.

    mode="quality": min cost s.t. mean quality >= threshold.
    mode="budget":  max quality s.t. total cost <= threshold.
    """

    mode: str = "quality"          # "quality" | "budget"
    iters: int = 150
    lr_constraint: float = 4.0     # α1 (quality) / µ step (budget, use ~50)
    lr_workload: float = 0.5       # α2 in Eq. 10
    use_kernel: bool = False       # fused Pallas dual ascent (1 launch/solve)
    block_q: int = 256             # query block for the fused kernel
    stall_tol: float = 0.0         # >0: early-exit on multiplier stall
    stall_patience: int = 3        # cumulative stalled iters before exit
    norm_grad: bool = False        # scale-free subgradient (streaming)
    shards: int = 1                # blocked stats reduction over the query
    #                                axis; under an active "query" mesh the
    #                                same blocks run one-per-device via
    #                                shard_map, bit-identical to shards on
    #                                one device (see the block comment above
    #                                _blocked_window_core)
    robust: bool = False           # route_window solves against the quality
    #                                lower-confidence-bound q - kappa*sigma
    kappa: float = 1.0             # LCB width (0 == bit-identical to robust
    #                                off: x - 0.0*sigma is exact for finite
    #                                sigma and no subgraph changes shape)

    def __post_init__(self):
        if self.mode not in ("quality", "budget"):
            raise ValueError(f"unknown solver mode: {self.mode!r}")
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1: {self.shards}")
        if self.kappa < 0.0:
            raise ValueError(f"kappa must be >= 0: {self.kappa}")

    # -- sharded/blocked dispatch ---------------------------------------------
    def _plan(self):
        """(mesh, axes, global shard count) honouring an active query mesh.

        No mesh (or no "query" rule): blocked single-device execution with
        ``self.shards`` blocks.  Active query mesh of D devices: the shard
        count adopts D (when ``shards`` is 1) or must be a multiple of it —
        each device then runs shards/D contiguous blocks."""
        from repro.common.sharding import query_axis_info
        qa = query_axis_info()
        if qa is None:
            return None, None, self.shards
        mesh, axes, d = qa
        gsh = self.shards if self.shards > 1 else d
        if gsh % d:
            raise ValueError(
                f"DualSolver.shards={gsh} must be a multiple of the active "
                f"query-mesh size {d}")
        return mesh, axes, gsh

    def _blocked_fn(self, mesh, axes, gshards: int, polish: bool):
        return _blocked_window_fn(
            mesh, axes, mode=self.mode, iters=self.iters,
            lr_con=self.lr_constraint, lr_load=self.lr_workload,
            patience=self.stall_patience, norm_grad=self.norm_grad,
            gshards=gshards, use_stats_kernel=self.use_kernel,
            bq=self.block_q, polish=polish)

    @staticmethod
    def _check_divisible(n: int, gshards: int):
        if n % gshards:
            raise ValueError(
                f"window size {n} does not divide into {gshards} query "
                f"shards — pad the window (StreamController pads to "
                f"power-of-two buckets and passes n_valid)")

    def solve(self, cost, quality, threshold, loads,
              state: Optional[DualState] = None, n_valid=None
              ) -> Tuple[jax.Array, SolveInfo]:
        """cost/quality (N, M) -> (assignment (N,), SolveInfo).

        ``state`` warm-starts the dual ascent from a previous window's
        multipliers (``threshold`` is used as given — ledger folding is
        ``route_window``'s job).  ``n_valid`` marks the valid-row prefix of
        a padded window (padding rows are masked out of every reduction)."""
        n, m = np.shape(cost)
        lam0 = jnp.zeros(()) if state is None else state.lam
        lam20 = jnp.zeros((m,)) if state is None else state.lam_load
        # continue the stream's step schedule, but keep a step floor
        # (~1/20) so a drifting workload can still move the multipliers
        step0 = (jnp.zeros(()) if state is None
                 else jnp.minimum(state.steps, 400.0))
        mesh, axes, gsh = self._plan()
        if mesh is not None or gsh > 1 or n_valid is not None:
            self._check_divisible(n, gsh)
            fn = self._blocked_fn(mesh, axes, gsh, polish=False)
            x, info, _, _ = fn(jnp.asarray(cost), jnp.asarray(quality),
                               threshold, jnp.asarray(loads), lam0, lam20,
                               self.stall_tol, step0,
                               n if n_valid is None else n_valid, threshold)
            return x, info
        if self.use_kernel:
            from repro.kernels.lagrangian_assign.ops import solve_fused
            return solve_fused(cost, quality, threshold, loads,
                               mode=self.mode, iters=self.iters,
                               lr_con=self.lr_constraint,
                               lr_load=self.lr_workload, bq=self.block_q,
                               lam0=lam0, lam20=lam20, step0=step0,
                               stall_tol=self.stall_tol,
                               patience=self.stall_patience,
                               norm_grad=self.norm_grad)
        return _solve_ref(jnp.asarray(cost), jnp.asarray(quality),
                          jnp.asarray(threshold, jnp.float32),
                          jnp.asarray(loads), lam0, lam20, self.stall_tol,
                          step0, mode=self.mode,
                          iters=self.iters, lr_con=self.lr_constraint,
                          lr_load=self.lr_workload,
                          patience=self.stall_patience,
                          norm_grad=self.norm_grad)

    def solve_batch(self, cost, quality, thresholds, loads):
        """vmap over a leading batch axis: cost/quality (B, N, M),
        thresholds (B,), loads (M,) or (B, M).

        Always runs the jit reference scan (``use_kernel`` is ignored here:
        the fused kernel is one launch per solve and is not vmapped)."""
        loads = jnp.asarray(loads)
        in_axes = (0, 0, 0, 0 if loads.ndim == 2 else None)
        fn = partial(_solve_ref, stall_tol=self.stall_tol,
                     mode=self.mode, iters=self.iters,
                     lr_con=self.lr_constraint, lr_load=self.lr_workload,
                     patience=self.stall_patience, norm_grad=self.norm_grad)
        return jax.vmap(fn, in_axes=in_axes)(
            jnp.asarray(cost), jnp.asarray(quality),
            jnp.asarray(thresholds, jnp.float32), loads)

    def solve_grid(self, cost, quality, thresholds, loads):
        """One compiled call sweeping a (K,) grid of alpha/budget thresholds
        over a single instance — bench_alpha / sweep workloads.

        Always runs the jit reference scan (``use_kernel`` is ignored here:
        the fused kernel is one launch per solve and is not vmapped)."""
        fn = partial(_solve_ref, stall_tol=self.stall_tol,
                     mode=self.mode, iters=self.iters,
                     lr_con=self.lr_constraint, lr_load=self.lr_workload,
                     patience=self.stall_patience, norm_grad=self.norm_grad)
        return jax.vmap(fn, in_axes=(None, None, 0, None))(
            jnp.asarray(cost), jnp.asarray(quality),
            jnp.asarray(thresholds, jnp.float32), jnp.asarray(loads))

    def route_arrays(self, cost, quality, threshold, loads,
                     polish_threshold=None,
                     state: Optional[DualState] = None, n_valid=None
                     ) -> Tuple[jax.Array, SolveInfo]:
        """Full device pipeline: solve -> workload repair -> primal polish.

        Blocked/sharded solves (``shards`` > 1, an active query mesh, or a
        masked window) run repair/polish shard-locally against an exact
        capacity partition inside the same fused program."""
        mesh, axes, gsh = self._plan()
        if mesh is not None or gsh > 1 or n_valid is not None:
            n, m = np.shape(cost)
            self._check_divisible(n, gsh)
            lam0 = jnp.zeros(()) if state is None else state.lam
            lam20 = jnp.zeros((m,)) if state is None else state.lam_load
            step0 = (jnp.zeros(()) if state is None
                     else jnp.minimum(state.steps, 400.0))
            pt = threshold if polish_threshold is None else polish_threshold
            fn = self._blocked_fn(mesh, axes, gsh, polish=True)
            x, info, _, _ = fn(jnp.asarray(cost), jnp.asarray(quality),
                               threshold, jnp.asarray(loads), lam0, lam20,
                               self.stall_tol, step0,
                               n if n_valid is None else n_valid, pt)
            return x, info
        x, info = self.solve(cost, quality, threshold, loads, state=state)
        cost = jnp.asarray(cost, jnp.float32)
        quality = jnp.asarray(quality, jnp.float32)
        loads = jnp.asarray(loads, jnp.float32)
        lam1 = info.lam if self.mode == "quality" else jnp.zeros(())
        x = repair_workload(x, cost, quality, loads, lam1=lam1)
        if self.mode == "quality":
            pt = threshold if polish_threshold is None else polish_threshold
            x = primal_polish(x, cost, quality,
                              jnp.asarray(pt, jnp.float32), loads)
        else:
            x = budget_polish(x, cost, quality,
                              jnp.asarray(threshold, jnp.float32), loads)
        return x, info

    def route_window(self, cost, quality, threshold, loads,
                     state: Optional[DualState] = None, *, share=1.0,
                     polish_margin: float = 0.0, n_valid=None,
                     quality_std=None
                     ) -> Tuple[jax.Array, SolveInfo, DualState]:
        """One streaming window: fold the cumulative ledger into this
        window's effective threshold, warm-start the ascent from the carried
        multipliers, repair/polish, and return the updated stream state.

        ``threshold`` is the GLOBAL constraint (stream budget B, or α);
        ``share`` is the window's fraction of the remaining horizon (budget
        mode only).  ``n_valid`` marks the valid-row prefix of a padded
        window — padding rows never touch the ledger (their cost/quality
        are zeroed and masked from every sum), so a power-of-two-padded
        stream charges exactly what it routed.  All ops are jnp, so the
        whole method traces into one jit (the router fuses
        predict→route_window into a single boundary).

        With ``robust=True`` the solve runs against the lower-confidence
        bound ``q - kappa*sigma`` (``quality_std`` when given, else the
        Bernoulli std of the predicted quality).  The substitution happens
        HERE, before mode dispatch, so every downstream path — legacy,
        fused kernel, blocked, mesh-sharded — and the ledger itself see
        the LCB: the quality ledger banks pessimistic qsum, so predictor
        error can only leave headroom, never overdraw the α constraint.
        """
        cost = jnp.asarray(cost, jnp.float32)
        quality = jnp.asarray(quality, jnp.float32)
        loads = jnp.asarray(loads, jnp.float32)
        if self.robust:
            if quality_std is None:
                qc = jnp.clip(quality, 0.0, 1.0)
                sigma = jnp.sqrt(qc * (1.0 - qc))
            else:
                sigma = jnp.asarray(quality_std, jnp.float32)
            quality = quality - jnp.float32(self.kappa) * sigma
        n, m = cost.shape
        if state is None:
            state = init_dual_state(m)
        threshold = jnp.asarray(threshold, jnp.float32)
        nv = n if n_valid is None else n_valid
        t_eff = fold_threshold(self.mode, threshold, state, nv, share)
        if self.mode == "quality":
            p_eff = jnp.clip(t_eff + polish_margin, 0.0, 1.0)
        else:
            p_eff = t_eff
        mesh, axes, gsh = self._plan()
        if mesh is not None or gsh > 1 or n_valid is not None:
            self._check_divisible(n, gsh)
            fn = self._blocked_fn(mesh, axes, gsh, polish=True)
            x, info, csum, qsum = fn(
                cost, quality, t_eff, loads, state.lam, state.lam_load,
                self.stall_tol, jnp.minimum(state.steps, 400.0), nv, p_eff)
        else:
            x, info = self.route_arrays(cost, quality, t_eff, loads,
                                        polish_threshold=p_eff, state=state)
            # ledger update uses the FINAL (repaired + polished) assignment
            csum = _chosen_sum(cost, x)
            qsum = _chosen_sum(quality, x)
        deficit = (threshold * nv - qsum) if self.mode == "quality" else 0.0
        new_state = DualState(
            lam=info.lam, lam_load=info.lam_load,
            budget_spent=state.budget_spent + csum,
            sr_deficit=state.sr_deficit + deficit,
            steps=state.steps + info.iters_run)
        if _sanitize.ENABLED and not isinstance(x, jax.core.Tracer):
            # opt-in sanitizer plane (repro.analysis.sanitize): ledger
            # conservation + an independent NumPy feasibility certificate.
            # Eager path only — under the router's fused predict->solve jit
            # everything here is a tracer and the host-level LedgerSan check
            # in StreamController/OmniRouter covers the window instead.
            _sanitize.check_route_window(
                mode=self.mode, x=x, cost=cost, quality=quality,
                threshold=threshold, t_eff=t_eff, loads=loads,
                state_in=state, state_out=new_state, csum=csum, qsum=qsum,
                n_valid=nv, info=info)
        return x, info, new_state


# --- legacy entry points: thin wrappers over the one DualSolver code path ---

def solve_assignment(cost, quality, alpha, loads, *, iters: int = 150,
                     lr_quality: float = 4.0, lr_workload: float = 0.5,
                     use_kernel: bool = False):
    """Quality-constrained mode. Returns (assignment (N,), SolveInfo)."""
    return DualSolver("quality", iters, lr_quality, lr_workload,
                      use_kernel).solve(cost, quality, alpha, loads)


def solve_budget(cost, quality, budget, loads, *, iters: int = 150,
                 lr_budget: float = 50.0, lr_workload: float = 0.5,
                 use_kernel: bool = False):
    """Budget mode: max (1/N)Σ a_ij x_ij  s.t. Σ c_ij x_ij <= B, loads."""
    return DualSolver("budget", iters, lr_budget, lr_workload,
                      use_kernel).solve(cost, quality, budget, loads)


# --- device-resident post-solve feasibility pass ------------------------------

@jax.jit
def repair_workload(x, cost, quality, loads, lam1=0.0, n_valid=None):
    """Enforce Σ_i x_ij <= L_j exactly by moving the cheapest-to-move queries
    off overloaded models (the scheduler must never violate concurrency
    limits).  One move per ``while_loop`` iteration: pick the most overloaded
    model, move its lowest-regret query to that query's best free model.
    ``n_valid`` (mask-padded windows) excludes padding rows — a suffix — from
    both the workload histogram and the move candidates.
    NumPy oracle: ``repro.kernels.lagrangian_assign.ref.repair_workload_ref``.
    """
    n, m = cost.shape
    x = jnp.asarray(x, jnp.int32)
    cost = jnp.asarray(cost, jnp.float32)
    quality = jnp.asarray(quality, jnp.float32)
    loads = jnp.asarray(loads, jnp.float32)
    reduced = cost - lam1 * quality / n
    inf = jnp.float32(jnp.inf)
    if n_valid is None:
        validr = None
        counts0 = jnp.zeros((m,), jnp.float32).at[x].add(1.0)
    else:
        validr = jnp.arange(n) < n_valid
        counts0 = jnp.zeros((m,), jnp.float32).at[x].add(
            validr.astype(jnp.float32))

    def cond(carry):
        _, _, done, k = carry
        return (~done) & (k < n)

    def body(carry):
        x, counts, _, k = carry
        over = counts - loads
        j = jnp.argmax(over)
        free = counts < loads
        # regret of moving each query off j to its best free alternative
        alt = jnp.where(free[None, :], reduced, inf)
        best_alt = jnp.argmin(alt, axis=1)
        alt_min = jnp.take_along_axis(alt, best_alt[:, None], axis=1)[:, 0]
        movable = (x == j) if validr is None else ((x == j) & validr)
        delta = jnp.where(movable, alt_min - reduced[:, j], inf)
        qi = jnp.argmin(delta)
        nj = best_alt[qi]
        do = (over[j] > 0) & jnp.any(free)   # saturated pool -> give up
        x_new = x.at[qi].set(nj.astype(jnp.int32))
        counts_new = counts.at[j].add(-1.0).at[nj].add(1.0)
        x = jnp.where(do, x_new, x)
        counts = jnp.where(do, counts_new, counts)
        return x, counts, ~do, k + 1

    x, _, _, _ = jax.lax.while_loop(
        cond, body, (x, counts0, jnp.asarray(False), jnp.asarray(0)))
    return x


@jax.jit
def primal_polish(x, cost, quality, alpha, loads, n_valid=None):
    """Greedy primal improvement, fully on device.  Phase 0 restores quality
    feasibility (best quality-gain-per-dollar moves); phase 1 is steepest-
    descent cost reduction (apply the single largest saving whose quality
    delta fits the constraint slack and whose target has capacity, until no
    improving move remains).  Closes most of the subgradient method's duality
    gap.  ``n_valid`` (mask-padded windows) excludes the padding suffix from
    the histogram, the quality target (nv·α, not n·α) and the move pool.
    NumPy oracle: ``...lagrangian_assign.ref.primal_polish_ref``."""
    n, m = cost.shape
    x = jnp.asarray(x, jnp.int32)
    cost = jnp.asarray(cost, jnp.float32)
    quality = jnp.asarray(quality, jnp.float32)
    loads = jnp.asarray(loads, jnp.float32)
    ninf = jnp.float32(-jnp.inf)
    inf = jnp.float32(jnp.inf)
    if n_valid is None:
        nv = n
        validc = None
        counts0 = jnp.zeros((m,), jnp.float32).at[x].add(1.0)
        qsum0 = jnp.take_along_axis(quality, x[:, None], axis=1).sum()
    else:
        nv = n_valid
        validr = jnp.arange(n) < n_valid
        validc = validr[:, None]
        vf = validr.astype(jnp.float32)
        counts0 = jnp.zeros((m,), jnp.float32).at[x].add(vf)
        qsum0 = (jnp.take_along_axis(quality, x[:, None], axis=1)[:, 0]
                 * vf).sum()

    def apply_move(x, counts, qsum, i, j, do):
        dq = quality[i, j] - quality[i, x[i]]
        x_new = x.at[i].set(j.astype(jnp.int32))
        counts_new = counts.at[x[i]].add(-1.0).at[j].add(1.0)
        return (jnp.where(do, x_new, x), jnp.where(do, counts_new, counts),
                jnp.where(do, qsum + dq, qsum))

    # phase 0 — restore quality feasibility if the dual left us short
    def cond0(carry):
        _, _, qsum, done, k = carry
        return (qsum < nv * alpha - 1e-9) & (~done) & (k < 4 * n)

    def body0(carry):
        x, counts, qsum, _, k = carry
        curq = jnp.take_along_axis(quality, x[:, None], axis=1)
        curc = jnp.take_along_axis(cost, x[:, None], axis=1)
        gain = quality - curq
        extra = cost - curc
        ok = (gain > 1e-12) & (counts[None, :] < loads[None, :])
        if validc is not None:
            ok = ok & validc
        score = jnp.where(ok, gain / jnp.maximum(extra, 1e-9), ninf)
        flat = jnp.argmax(score)
        i, j = flat // m, flat % m
        do = score.reshape(-1)[flat] > ninf
        x, counts, qsum = apply_move(x, counts, qsum, i, j, do)
        return x, counts, qsum, ~do, k + 1

    x, counts, qsum, _, _ = jax.lax.while_loop(
        cond0, body0, (x, counts0, qsum0, jnp.asarray(False), jnp.asarray(0)))

    # phase 1 — steepest-descent cost reduction within the quality slack
    def cond1(carry):
        _, _, _, done, k = carry
        return (~done) & (k < 8 * n)

    def body1(carry):
        x, counts, qsum, _, k = carry
        curq = jnp.take_along_axis(quality, x[:, None], axis=1)
        curc = jnp.take_along_axis(cost, x[:, None], axis=1)
        slack = qsum - nv * alpha
        delta = cost - curc                   # <0 == cheaper
        dq = quality - curq
        ok = (delta < -1e-12) & (counts[None, :] < loads[None, :]) & \
            (dq >= -slack - 1e-12)
        if validc is not None:
            ok = ok & validc
        score = jnp.where(ok, delta, inf)
        flat = jnp.argmin(score)
        i, j = flat // m, flat % m
        do = score.reshape(-1)[flat] < inf
        x, counts, qsum = apply_move(x, counts, qsum, i, j, do)
        return x, counts, qsum, ~do, k + 1

    x, _, _, _, _ = jax.lax.while_loop(
        cond1, body1, (x, counts, qsum, jnp.asarray(False), jnp.asarray(0)))
    return x


@jax.jit
def budget_polish(x, cost, quality, budget, loads, n_valid=None):
    """Budget-mode primal improvement (symmetric to ``primal_polish``).

    Phase 0 restores budget feasibility when the dual left us over budget
    (e.g. an infeasible B): repeatedly apply the cost-reducing move that
    loses the least quality per dollar saved.  Phase 1 is steepest quality
    ascent — apply the single largest quality gain whose extra cost fits the
    remaining budget and whose target model has capacity.  ``n_valid``
    (mask-padded windows) excludes the padding suffix from the histogram and
    the move pool.
    NumPy oracle: ``...lagrangian_assign.ref.budget_polish_ref``."""
    n, m = cost.shape
    x = jnp.asarray(x, jnp.int32)
    cost = jnp.asarray(cost, jnp.float32)
    quality = jnp.asarray(quality, jnp.float32)
    loads = jnp.asarray(loads, jnp.float32)
    ninf = jnp.float32(-jnp.inf)
    if n_valid is None:
        validc = None
        counts0 = jnp.zeros((m,), jnp.float32).at[x].add(1.0)
        csum0 = jnp.take_along_axis(cost, x[:, None], axis=1).sum()
    else:
        validr = jnp.arange(n) < n_valid
        validc = validr[:, None]
        vf = validr.astype(jnp.float32)
        counts0 = jnp.zeros((m,), jnp.float32).at[x].add(vf)
        csum0 = (jnp.take_along_axis(cost, x[:, None], axis=1)[:, 0]
                 * vf).sum()

    def apply_move(x, counts, csum, i, j, do):
        dc = cost[i, j] - cost[i, x[i]]
        x_new = x.at[i].set(j.astype(jnp.int32))
        counts_new = counts.at[x[i]].add(-1.0).at[j].add(1.0)
        return (jnp.where(do, x_new, x), jnp.where(do, counts_new, counts),
                jnp.where(do, csum + dc, csum))

    def cond0(carry):
        _, _, csum, done, k = carry
        return (csum > budget + 1e-9) & (~done) & (k < 4 * n)

    def body0(carry):
        x, counts, csum, _, k = carry
        curq = jnp.take_along_axis(quality, x[:, None], axis=1)
        curc = jnp.take_along_axis(cost, x[:, None], axis=1)
        dq = quality - curq
        dc = cost - curc
        ok = (dc < -1e-12) & (counts[None, :] < loads[None, :])
        if validc is not None:
            ok = ok & validc
        # least quality lost per dollar saved
        score = jnp.where(ok, dq / jnp.maximum(-dc, 1e-9), ninf)
        flat = jnp.argmax(score)
        i, j = flat // m, flat % m
        do = score.reshape(-1)[flat] > ninf
        x, counts, csum = apply_move(x, counts, csum, i, j, do)
        return x, counts, csum, ~do, k + 1

    x, counts0, csum0, _, _ = jax.lax.while_loop(
        cond0, body0, (x, counts0, csum0, jnp.asarray(False), jnp.asarray(0)))

    def cond(carry):
        _, _, _, done, k = carry
        return (~done) & (k < 8 * n)

    def body(carry):
        x, counts, csum, _, k = carry
        curq = jnp.take_along_axis(quality, x[:, None], axis=1)
        curc = jnp.take_along_axis(cost, x[:, None], axis=1)
        dq = quality - curq
        dc = cost - curc
        ok = (dq > 1e-12) & (counts[None, :] < loads[None, :]) & \
            (csum + dc <= budget + 1e-9)
        if validc is not None:
            ok = ok & validc
        score = jnp.where(ok, dq, ninf)
        flat = jnp.argmax(score)
        i, j = flat // m, flat % m
        do = score.reshape(-1)[flat] > ninf
        x, counts, csum = apply_move(x, counts, csum, i, j, do)
        return x, counts, csum, ~do, k + 1

    x, _, _, _, _ = jax.lax.while_loop(
        cond, body, (x, counts0, csum0, jnp.asarray(False), jnp.asarray(0)))
    return x


def brute_force(cost: np.ndarray, quality: np.ndarray, threshold: float,
                loads: np.ndarray, mode: str = "quality"
                ) -> Optional[np.ndarray]:
    """Exact solver for tiny instances (test oracle), both modes."""
    import itertools
    n, m = cost.shape
    best, best_obj = None, np.inf
    for x in itertools.product(range(m), repeat=n):
        x = np.array(x)
        if np.any(np.bincount(x, minlength=m) > loads):
            continue
        q = quality[np.arange(n), x].mean()
        c = cost[np.arange(n), x].sum()
        if mode == "quality":
            if q < threshold:
                continue
            obj = c
        else:
            if c > threshold:
                continue
            obj = -q * n
        if obj < best_obj:
            best, best_obj = x, obj
    return best
