"""Fused Lagrangian dual ascent (ECCOS optimizer, Eq. 9-12) in ONE kernel.

``fused_dual_solve`` runs the *entire* dual-ascent loop inside a single
``pallas_call``: grid = (iters, query_blocks), with the scalar multiplier
λ (or µ), the per-model workload multipliers λ2, the iteration histogram and
the multipliers of the best-feasible iterate carried in scratch across grid
steps.  This replaces the seed's one-``pallas_call``-per-iteration structure
(150 launches per solve) with exactly one launch.

The kernel is mode-agnostic: it sees the unified parameterization

    scores_ij = A_ij + lam * B_ij + lam2_j,   feasible ⇔ Σ B[i, x_i] <= t

(quality mode: A = cost, B = -quality/N, t = -alpha; budget mode:
A = -quality, B = cost, t = B — see ``repro.core.optimizer``).

No N-sized state ever crosses an iteration: instead of storing the
best-feasible *assignment*, the kernel stores the multipliers that produced
it — argmin is deterministic, so the caller (``ops.solve_fused``) replays
the winning assignment from those multipliers in one vectorized argmin.
Padded rows (N not a multiple of the query block) are masked out of every
histogram/sum in-kernel.

Streaming (ISSUE 5): both kernel layouts take warm-start multipliers
(λ0 via the scalar vector, λ2_0 as a second row of the aux/loads input) so
a windowed stream resumes the ascent from the previous window's dual point,
and both implement early exit by *freezing*: once a feasible iterate is
banked and ``patience`` iterations (cumulative) have stalled (multiplier
movement or constraint residual under ``stall_tol``), the dual update stops
being applied — remaining grid steps recompute identical values, so the
emitted multipliers and ``iters_run`` match the reference while_loop's
early exit exactly (a Pallas grid cannot shrink dynamically, so freezing
is the device-side equivalent).

``assign_step_kernel`` (one fused argmin + histogram step) is kept as the
single-step building block and micro-benchmark target.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def backend_interpret(interpret: Optional[bool] = None) -> bool:
    """Auto-select interpret mode by backend: compiled on TPU, interpreted
    elsewhere (CPU/GPU have no Mosaic lowering for these kernels)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# slot layout of the (8,) SMEM scalar state / scalar output
_LAM, _LAM_BEST, _BEST, _FOUND, _ASUM, _BSUM, _TRUN, _STALL = range(8)
# row layout of the (3, m) vector state / vector output
_L2, _L2B, _CNT = range(3)

# Mosaic layout rules shared by every kernel here: scalars live in SMEM
# (read and written one element at a time), vectors in VMEM as 2-D (rows, m)
# blocks whose shape equals the array's; per-query results are (rows, 1)
# columns.  Nothing is read from an ANY-space ref (only DMAs may touch one)
# and no scalar is stored into VMEM.


def _argmin_rows(scores):
    """Row argmin as a (rows, 1) int32 column — ties go to the lowest
    column, exactly like ``jnp.argmin``."""
    cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    mn = scores.min(axis=1, keepdims=True)
    big = jnp.int32(scores.shape[1])
    return jnp.where(scores == mn, cols, big).min(axis=1, keepdims=True)


def _fused_kernel(scal_ref, ab_ref, aux_ref, sout_ref, vout_ref, smem, vec, *,
                  n: int, m: int, bq: int, masked: bool, patience: int):
    t = pl.program_id(0)
    b = pl.program_id(1)
    thresh = scal_ref[0]
    lr_eff = scal_ref[1]
    lr_load = scal_ref[2]
    lam0 = scal_ref[3]
    stall_tol = scal_ref[4]
    step0 = scal_ref[5]
    loads = aux_ref[0:1, :]                                  # (1, m)

    @pl.when((t == 0) & (b == 0))
    def _init():
        smem[_LAM] = lam0
        smem[_LAM_BEST] = 0.0
        smem[_BEST] = jnp.float32(jnp.inf)
        smem[_FOUND] = 0.0
        smem[_ASUM] = 0.0
        smem[_BSUM] = 0.0
        smem[_STALL] = 0.0
        smem[_TRUN] = 0.0
        vec[...] = jnp.zeros_like(vec)
        vec[_L2:_L2 + 1, :] = aux_ref[1:2, :]                # warm-start λ2

    @pl.when((t > 0) & (b == 0))
    def _finalize_prev_iter():
        # iteration t-1's stats are complete: best-feasible bookkeeping +
        # dual update (Eq. 9-12) before any block of iteration t runs.
        # The whole finalize is gated on the freeze flag: past `patience`
        # stalled updates the multipliers stop moving, every later iteration
        # recomputes the same assignment, and — like the reference
        # while_loop, which exits outright — none of it is bookkept.
        @pl.when(smem[_STALL] < jnp.float32(patience))
        def _bookkeep_and_update():
            asum = smem[_ASUM]
            bsum = smem[_BSUM]
            cnt = vec[_CNT:_CNT + 1, :]
            lam2 = vec[_L2:_L2 + 1, :]
            over = jnp.where(cnt <= loads, 0.0, 1.0).max()
            feasible = (bsum <= thresh) & (over == 0.0)
            better = feasible & (asum < smem[_BEST])

            @pl.when(better)
            def _commit_best():
                smem[_BEST] = asum
                smem[_LAM_BEST] = smem[_LAM]
                vec[_L2B:_L2B + 1, :] = lam2

            smem[_FOUND] = jnp.where(feasible, 1.0, smem[_FOUND])
            # diminishing step 1/sqrt(1 + step0 + (t-1)), continuing the
            # stream's schedule for subgradient convergence
            step = jax.lax.rsqrt(step0 + t.astype(jnp.float32))
            lam_new = jnp.maximum(
                smem[_LAM] + lr_eff * step * (bsum - thresh), 0.0)
            lam2_new = jnp.maximum(lam2 + lr_load * step * (cnt - loads), 0.0)
            delta = (jnp.abs(lam_new - smem[_LAM])
                     + jnp.abs(lam2_new - lam2).sum())
            denom = 1.0 + jnp.abs(lam_new) + jnp.abs(lam2_new).sum()
            resid = jnp.abs(bsum - thresh) / (1.0 + jnp.abs(thresh))
            stalled = (smem[_FOUND] > 0.0) & ((delta < stall_tol * denom)
                                              | (resid < stall_tol))
            # cumulative count — see the reference body in core.optimizer
            smem[_STALL] += jnp.where(stalled, 1.0, 0.0)
            smem[_TRUN] += 1.0
            smem[_LAM] = lam_new
            vec[_L2:_L2 + 1, :] = lam2_new

        smem[_ASUM] = 0.0
        smem[_BSUM] = 0.0
        vec[_CNT:_CNT + 1, :] = jnp.zeros_like(loads)

    ab = ab_ref[...].astype(jnp.float32)                     # (bq, 2m)
    a = ab[:, :m]
    bm = ab[:, m:]
    scores = a + smem[_LAM] * bm + vec[_L2:_L2 + 1, :]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 1)
    onehot = _argmin_rows(scores) == cols
    if masked:                                               # strip padded rows
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 0)
        onehot = onehot & ((b * bq + rows) < n)
    onehot = onehot.astype(jnp.float32)
    vec[_CNT:_CNT + 1, :] += onehot.sum(axis=0, keepdims=True)
    smem[_ASUM] += (a * onehot).sum()
    smem[_BSUM] += (bm * onehot).sum()

    # every visit writes the (tiny) outputs; the last visit's values — the
    # multiplier state plus the final iteration's complete statistics — are
    # what the caller reads.  The best/last assignments themselves are
    # recomputed OUTSIDE the kernel from these multipliers (argmin is
    # deterministic), so no N-sized state ever leaves the loop.
    for i in range(8):
        sout_ref[i] = smem[i]
    vout_ref[...] = vec[...]


def _fused_kernel_whole(scal_ref, ab_ref, aux_ref, sout_ref, vout_ref, *,
                        m: int, bq: int, iters: int, patience: int):
    """Single-block variant: the whole instance fits one query block (which
    also means no padded rows: bq == n), so the dual-ascent loop is a
    fori_loop over pure values inside one grid step — no per-iteration grid
    bookkeeping at all.  Early exit is the same freeze as the grid layout
    (a fori_loop trip count is static): once stalled past ``patience`` the
    carried multipliers stop changing and ``t_run`` stops counting.
    Same update rule as the multi-block kernel; output layout as documented
    in ``fused_dual_solve``."""
    thresh = scal_ref[0]
    lr_eff = scal_ref[1]
    lr_load = scal_ref[2]
    lam0 = scal_ref[3]
    stall_tol = scal_ref[4]
    step0 = scal_ref[5]
    loads = aux_ref[0:1, :]                                  # (1, m)
    lam20 = aux_ref[1:2, :]
    ab = ab_ref[...].astype(jnp.float32)
    a = ab[:, :m]
    bm = ab[:, m:]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 1)

    def body(t, carry):
        lam, lam2, lam_best, lam2_best, best, found, stall, t_run = carry
        active = stall < patience
        # assign + stats + finalize all inside the iteration (the reference
        # flow): no cross-iteration stats carry needed with a single block
        scores = a + lam * bm + lam2
        onehot = (_argmin_rows(scores) == cols).astype(jnp.float32)
        # per-row chosen value first, then one sum over rows: the same
        # reduction shape as the reference's gather-then-sum
        asum = (a * onehot).sum(axis=1, keepdims=True).sum()
        bsum = (bm * onehot).sum(axis=1, keepdims=True).sum()
        cnt = onehot.sum(axis=0, keepdims=True)              # (1, m)
        over = jnp.where(cnt <= loads, 0.0, 1.0).max()
        # bookkeeping is gated on `active` so a frozen (early-exited) solve
        # matches the reference while_loop, which never sees the iterate it
        # exited on
        feasible = (active & (bsum <= thresh) & (over == 0.0)).astype(
            jnp.float32)
        better = feasible * (asum < best).astype(jnp.float32)
        best = jnp.where(better > 0.0, asum, best)
        lam_best = jnp.where(better > 0.0, lam, lam_best)
        lam2_best = jnp.where(better > 0.0, lam2, lam2_best)
        found = jnp.maximum(found, feasible)
        step = jax.lax.rsqrt(1.0 + step0 + t.astype(jnp.float32))
        lam_new = jnp.maximum(lam + lr_eff * step * (bsum - thresh), 0.0)
        lam2_new = jnp.maximum(lam2 + lr_load * step * (cnt - loads), 0.0)
        delta = (jnp.abs(lam_new - lam) + jnp.abs(lam2_new - lam2).sum())
        denom = 1.0 + jnp.abs(lam_new) + jnp.abs(lam2_new).sum()
        resid = jnp.abs(bsum - thresh) / (1.0 + jnp.abs(thresh))
        stalled = (found > 0.0) & ((delta < stall_tol * denom)
                                   | (resid < stall_tol))
        # cumulative count — see the reference body in core.optimizer
        stall = stall + jnp.where(active & stalled, 1, 0)
        lam = jnp.where(active, lam_new, lam)
        lam2 = jnp.where(active, lam2_new, lam2)
        t_run = t_run + jnp.where(active, 1, 0)
        return lam, lam2, lam_best, lam2_best, best, found, stall, t_run

    zero_m = jnp.zeros((1, m), jnp.float32)
    init = (lam0, lam20, jnp.float32(0.0), zero_m,
            jnp.float32(jnp.inf), jnp.float32(0.0),
            jnp.int32(0), jnp.int32(0))
    lam, lam2, lam_best, lam2_best, best, found, _, t_run = jax.lax.fori_loop(
        0, iters, body, init)
    # every iteration is fully finalized here, so scalar slots 4/5/7 and the
    # histogram row are unused; ops.solve_fused skips its finalize for the
    # single-block layout
    vals = {_LAM: lam, _LAM_BEST: lam_best, _BEST: best, _FOUND: found,
            _TRUN: t_run.astype(jnp.float32)}
    for i in range(8):
        sout_ref[i] = vals.get(i, jnp.float32(0.0))
    vout_ref[_L2:_L2 + 1, :] = lam2
    vout_ref[_L2B:_L2B + 1, :] = lam2_best
    vout_ref[_CNT:_CNT + 1, :] = zero_m


def fused_dual_solve(a_mat, b_mat, thresh, loads, *, iters: int = 150,
                     lr_eff: float, lr_load: float, bq: int = 256,
                     lam0=0.0, lam20=None, stall_tol=0.0, step0=0.0,
                     patience: int = 3,
                     interpret: Optional[bool] = None):
    """Run the full dual-ascent loop in one kernel launch.

    a_mat/b_mat (N, M) unified score matrices; thresh scalar; loads (M,);
    lam0 / lam20 warm-start the multipliers (streaming windows); stall_tol
    > 0 freezes the ascent once the relative multiplier movement stays
    below it for ``patience`` cumulative updates after a feasible iterate
    was banked.  Returns (scalars (8,) f32, vectors (3, M) f32,
    n_query_blocks):
    scalars = [lam, lam_best, best_objective, found, last ΣA, last ΣB,
               updates_applied, stall_count],
    vectors = [lam2; lam2_best; last histogram]
    — the multiplier state after the loop (plus, for the multi-block grid
    layout, the final iteration's statistics, which the caller must still
    finalize *iff* stall_count < patience).  The caller recomputes the
    best/last assignment from the multipliers (see ``ops.solve_fused``).
    """
    n, m = a_mat.shape
    bq = min(bq, n)
    pad = (-n) % bq
    ab = jnp.concatenate([a_mat, b_mat], axis=1)             # (N, 2M)
    if pad:
        ab = jnp.concatenate([ab, jnp.zeros((pad, 2 * m), ab.dtype)], axis=0)
    nb = (n + pad) // bq
    scal = jnp.stack([jnp.asarray(thresh, jnp.float32),
                      jnp.asarray(lr_eff, jnp.float32),
                      jnp.asarray(lr_load, jnp.float32),
                      jnp.asarray(lam0, jnp.float32),
                      jnp.asarray(stall_tol, jnp.float32),
                      jnp.asarray(step0, jnp.float32)])

    loads = jnp.asarray(loads, jnp.float32)
    if lam20 is None:
        lam20 = jnp.zeros((m,), jnp.float32)
    # loads + warm-start λ2 packed as one (2, m) aux input
    aux = jnp.stack([loads, jnp.asarray(lam20, jnp.float32)])
    out_shape = [jax.ShapeDtypeStruct((8,), jnp.float32),
                 jax.ShapeDtypeStruct((3, m), jnp.float32)]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if nb == 1:
        # whole instance in one block (bq == n, so no padding): run the
        # loop inside a single grid step
        kernel = functools.partial(_fused_kernel_whole, m=m, bq=bq,
                                   iters=iters, patience=patience)
        sout, vout = pl.pallas_call(
            kernel,
            grid=(1,),
            in_specs=[
                smem,                                        # scalars
                pl.BlockSpec((bq, 2 * m), lambda i: (0, 0)),  # A | B packed
                pl.BlockSpec((2, m), lambda i: (0, 0)),      # loads | λ2_0
            ],
            out_specs=[smem, pl.BlockSpec((3, m), lambda i: (0, 0))],
            out_shape=out_shape,
            interpret=backend_interpret(interpret),
        )(scal, ab, aux)
        return sout, vout, 1

    kernel = functools.partial(_fused_kernel, n=n, m=m, bq=bq,
                               masked=bool(pad), patience=patience)
    sout, vout = pl.pallas_call(
        kernel,
        grid=(iters, nb),
        in_specs=[
            smem,                                            # scalars
            pl.BlockSpec((bq, 2 * m), lambda t, b: (b, 0)),  # A | B packed
            pl.BlockSpec((2, m), lambda t, b: (0, 0)),       # loads | λ2_0
        ],
        out_specs=[smem, pl.BlockSpec((3, m), lambda t, b: (0, 0))],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.SMEM((8,), jnp.float32),                   # scalar state
            pltpu.VMEM((3, m), jnp.float32),                 # λ2 | λ2@best | histogram
        ],
        interpret=backend_interpret(interpret),
    )(scal, ab, aux)
    return sout, vout, nb


def _shard_stats_kernel(scal_ref, ab_ref, aux_ref, sums_ref, cnt_ref, *,
                        m: int, bq: int, bps: int):
    """One dual-ascent iteration's statistics, accumulated PER SHARD.

    The mesh-sharded solver (ISSUE 6) cannot run the whole ascent loop in
    one launch — the dual update needs a cross-device reduction every
    iteration — so the sharded ``use_kernel`` path calls this kernel once
    per iteration: grid = (shards * blocks_per_shard,), each block adds its
    argmin assignment's [ΣA, ΣB] into its shard's SMEM row and its
    histogram into its shard's VMEM row.  Per-shard accumulation is
    sequential in grid order, so the partials are bit-identical whether all
    shards run on one device (blocked reference) or each device handles one
    shard under ``shard_map``.

    scal = [λ, nv_0..nv_{S-1}] (per-shard valid-row counts — rows at or past
    a shard's bound are padding and touch nothing); aux row 0 = λ2."""
    b = pl.program_id(0)
    s = b // bps
    lam = scal_ref[0]
    bound = scal_ref[1 + s].astype(jnp.int32)

    @pl.when(b % bps == 0)
    def _init():
        sums_ref[s, 0] = 0.0
        sums_ref[s, 1] = 0.0
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    ab = ab_ref[...].astype(jnp.float32)                     # (bq, 2m)
    a = ab[:, :m]
    bm = ab[:, m:]
    scores = a + lam * bm + aux_ref[...]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 0)
    onehot = (_argmin_rows(scores) == cols) & (((b % bps) * bq + rows) < bound)
    ohf = onehot.astype(jnp.float32)
    sums_ref[s, 0] += (a * ohf).sum()
    sums_ref[s, 1] += (bm * ohf).sum()
    cnt_ref[0] += ohf.sum(axis=0, keepdims=True)


def shard_stats(a_mat, b_mat, lam, lam2, nv, *, lblocks: int, bq: int = 256,
                interpret: Optional[bool] = None):
    """Per-shard [ΣA, ΣB, histogram] partials for one dual iteration.

    a_mat/b_mat (lblocks*nl, M) — ``lblocks`` contiguous query shards; nv
    (lblocks,) per-shard valid-row counts.  Returns (lblocks, 2+M) f32."""
    nloc, m = a_mat.shape
    nl = nloc // lblocks
    bq = min(bq, nl)
    pad = (-nl) % bq
    ab = jnp.concatenate([a_mat, b_mat], axis=1).reshape(lblocks, nl, 2 * m)
    if pad:
        ab = jnp.concatenate(
            [ab, jnp.zeros((lblocks, pad, 2 * m), ab.dtype)], axis=1)
    ab = ab.reshape(lblocks * (nl + pad), 2 * m)
    bps = (nl + pad) // bq
    scal = jnp.concatenate([jnp.reshape(lam, (1,)),
                            jnp.asarray(nv, jnp.float32)]).astype(jnp.float32)
    kernel = functools.partial(_shard_stats_kernel, m=m, bq=bq, bps=bps)
    sums, cnt = pl.pallas_call(
        kernel,
        grid=(lblocks * bps,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # λ | nv per shard
            pl.BlockSpec((bq, 2 * m), lambda i: (i, 0)),     # A | B packed
            pl.BlockSpec((1, m), lambda i: (0, 0)),          # λ2
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # [ΣA, ΣB] rows
            pl.BlockSpec((1, 1, m), lambda i: (i // bps, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((lblocks, 2), jnp.float32),
                   jax.ShapeDtypeStruct((lblocks, 1, m), jnp.float32)],
        interpret=backend_interpret(interpret),
    )(scal, ab, jnp.asarray(lam2, jnp.float32)[None, :])
    return jnp.concatenate([sums, cnt[:, 0, :]], axis=1)


def _step_kernel(lam1_ref, c_ref, a_ref, lam2_ref, x_ref, cnt_ref, sums_ref,
                 *, n: int, m: int, bq: int):
    iq = pl.program_id(0)

    @pl.when(iq == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        sums_ref[0] = 0.0
        sums_ref[1] = 0.0

    c = c_ref[...].astype(jnp.float32)                       # (BQ, M)
    a = a_ref[...].astype(jnp.float32)
    scores = c - lam1_ref[0] * a / n + lam2_ref[...]
    x = _argmin_rows(scores)                                 # (BQ, 1)
    x_ref[...] = x
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, m), 0)
    valid = (iq * bq + rows) < n                             # mask padded rows
    onehot_f = ((x == cols) & valid).astype(jnp.float32)
    cnt_ref[...] += onehot_f.sum(axis=0, keepdims=True)
    sums_ref[0] += (a * onehot_f).sum()
    sums_ref[1] += (c * onehot_f).sum()


def assign_step_kernel(cost, quality, lam1, lam2, *, bq: int = 256,
                       interpret: Optional[bool] = None):
    """One fused reduced-cost argmin step: cost/quality (N, M); lam1 scalar;
    lam2 (M,).  Returns (x (N,), counts (M,), qsum, csum).  Padded rows are
    masked from the histogram in-kernel."""
    n, m = cost.shape
    bq = min(bq, n)
    pad = (-n) % bq
    if pad:
        cost = jnp.concatenate([cost, jnp.zeros((pad, m), cost.dtype)], axis=0)
        quality = jnp.concatenate(
            [quality, jnp.zeros((pad, m), quality.dtype)], axis=0)
    npad = cost.shape[0]

    kernel = functools.partial(_step_kernel, n=n, m=m, bq=bq)
    x, counts, sums = pl.pallas_call(
        kernel,
        grid=(npad // bq,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),           # λ1
            pl.BlockSpec((bq, m), lambda i: (i, 0)),
            pl.BlockSpec((bq, m), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),          # λ2
        ],
        out_specs=[
            pl.BlockSpec((bq, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.float32),
        ],
        interpret=backend_interpret(interpret),
    )(jnp.reshape(lam1, (1,)).astype(jnp.float32), cost, quality,
      jnp.asarray(lam2, jnp.float32)[None, :])
    return x[:n, 0], counts[0], sums[0], sums[1]
