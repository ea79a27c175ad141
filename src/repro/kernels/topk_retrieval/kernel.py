"""Fused cosine-similarity + running top-k (+ neighbour vote) Pallas kernels
(the ECCOS-R / ECCOS-H hot loop).

``topk_retrieval_kernel`` — grid (n_q_blocks, n_db_tiles), db tiles
innermost.  Each step computes the (BQ, TILE) similarity block on the MXU,
then folds it into a running top-k held in VMEM scratch via k iterations of
(max, argmax, mask) — k is small (4..64 per the paper's Table 4) so the fold
is VPU-cheap relative to the matmul.  The vector store never leaves HBM more
than once per query block.

``retrieval_vote_kernel`` — the same fold extended with a second phase over
the db tiles (grid (n_q_blocks, 2, n_db_tiles)) that turns the finished
top-k index set into per-model neighbour-mean labels WITHOUT a host gather:
phase 1 rebuilds a {0,1} membership matrix per (query, db-row-in-tile) from
the scratch indices and accumulates ``membership @ labels_tile`` on the MXU.
One launch returns (vals, idx, votes) — sim → top-k → gather-labels → vote.

Store sizes need not be tile multiples: the store is zero-padded up to the
tile grid and padded columns are masked to NEG_INF before the fold (the seed
asserted ``n_db % tile == 0`` and crashed on e.g. N_db=700).  ``n_valid`` is
a *dynamic* scalar (SMEM) so an incrementally growing ``VectorStore`` only
recompiles on capacity doubling, not on every append.  Slots beyond the
number of valid candidates (k > n_valid) come back as (NEG_INF, -1) and are
excluded from the vote denominator (the seed zero-initialized the index
scratch, silently aliasing empty slots to db row 0's labels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fold_topk(v_scr, i_scr, sims, col, k: int):
    """Fold a (BQ, TILE) sim block into the running (BQ, k) top-k scratch.

    Candidate indices are pairwise distinct (previous picks hold columns from
    earlier tiles; ``col`` covers this tile), so k rounds of extract-max give
    the exact running top-k.  Each round picks the LOWEST db index holding
    the maximum, matching ``jax.lax.top_k``'s tie order.  Exhausted rounds
    (all remaining candidates at NEG_INF) record index -1, never a real row.

    Everything is a lane reduction or an iota compare-and-select: Mosaic
    lowers no in-kernel gather or scatter.  Indices ride in f32 (exact below
    2**24 rows) because the lane min-reduction is a float op.
    """
    prev_v = v_scr[...]                                      # (BQ, k)
    prev_i = i_scr[...].astype(jnp.float32)
    colf = col.astype(jnp.float32)                           # (BQ, TILE)
    slot = jax.lax.broadcasted_iota(jnp.int32, prev_v.shape, 1)
    big = jnp.float32(2 ** 30)
    new_v = jnp.full(prev_v.shape, NEG_INF, jnp.float32)
    new_i = jnp.full(prev_v.shape, -1.0, jnp.float32)
    for r in range(k):
        m = jnp.maximum(prev_v.max(axis=1, keepdims=True),
                        sims.max(axis=1, keepdims=True))     # (BQ, 1)
        picked = jnp.minimum(
            jnp.where(prev_v == m, prev_i, big).min(axis=1, keepdims=True),
            jnp.where(sims == m, colf, big).min(axis=1, keepdims=True))
        picked = jnp.where(m > NEG_INF * 0.5, picked, -1.0)
        new_v = jnp.where(slot == r, m, new_v)
        new_i = jnp.where(slot == r, picked, new_i)
        prev_v = jnp.where(prev_i == picked, NEG_INF, prev_v)
        sims = jnp.where(colf == picked, NEG_INF, sims)
    v_scr[...] = new_v
    i_scr[...] = new_i.astype(jnp.int32)


def _masked_sims(q_ref, db_ref, nv_ref, it, tile: int):
    """(BQ, TILE) similarity block with db rows >= n_valid masked out."""
    sims = jax.lax.dot_general(q_ref[...], db_ref[...], (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    col = it * tile + jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
    return jnp.where(col < nv_ref[0], sims, NEG_INF), col


def _topk_kernel(nv_ref, q_ref, db_ref, vals_ref, idx_ref, v_scr, i_scr, *,
                 k: int, tile: int, n_tiles: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        v_scr[...] = jnp.full_like(v_scr, NEG_INF)
        i_scr[...] = jnp.full_like(i_scr, -1)

    sims, col = _masked_sims(q_ref, db_ref, nv_ref, it, tile)
    _fold_topk(v_scr, i_scr, sims, col, k)

    @pl.when(it == n_tiles - 1)
    def _finish():
        vals_ref[...] = v_scr[...]
        idx_ref[...] = i_scr[...]


def _vote_kernel(nv_ref, q_ref, db_ref, lab_ref, vals_ref, idx_ref, vote_ref,
                 v_scr, i_scr, acc_scr, *, k: int, tile: int, n_tiles: int):
    ph = pl.program_id(1)
    it = pl.program_id(2)

    @pl.when((ph == 0) & (it == 0))
    def _init():
        v_scr[...] = jnp.full_like(v_scr, NEG_INF)
        i_scr[...] = jnp.full_like(i_scr, -1)

    @pl.when(ph == 0)
    def _sim_phase():
        sims, col = _masked_sims(q_ref, db_ref, nv_ref, it, tile)
        _fold_topk(v_scr, i_scr, sims, col, k)

    @pl.when(ph == 1)
    def _vote_phase():
        @pl.when(it == 0)
        def _zero():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # membership of each db row of this tile in the finished top-k set
        # (indices are distinct so the sum is {0,1}); empty slots hold -1 and
        # never match a real column
        col = it * tile + jax.lax.broadcasted_iota(
            jnp.int32, (v_scr.shape[0], tile), 1)
        idxs = i_scr[...]
        member = jnp.zeros(col.shape, jnp.float32)
        for r in range(k):
            member += (col == idxs[:, r:r + 1]).astype(jnp.float32)
        acc_scr[...] += jax.lax.dot_general(
            member, lab_ref[...], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

        @pl.when(it == n_tiles - 1)
        def _finish():
            n_nb = (v_scr[...] > NEG_INF * 0.5).astype(jnp.float32).sum(
                axis=1, keepdims=True)
            vote_ref[...] = acc_scr[...] / jnp.maximum(n_nb, 1.0)
            vals_ref[...] = v_scr[...]
            idx_ref[...] = i_scr[...]


def _pad_rows(x, pad: int):
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _grid_geometry(n_db: int, b: int, bq: int, tile: int):
    """Clamp the tile to the (rounded-up) store and pad both axes."""
    tile = max(8, min(tile, -(-n_db // 8) * 8))
    pad_db = (-n_db) % tile
    pad_b = (-b) % bq
    return tile, pad_db, pad_b, (n_db + pad_db) // tile


def topk_retrieval_kernel(store, queries, k: int, *, bq: int = 128,
                          tile: int = 512, interpret: bool = True,
                          n_valid=None):
    """store (N_db, d); queries (B, d). Returns (vals (B, k), idx (B, k)).

    Works for any store size (padded in-kernel) and any k: slots past the
    number of valid rows return (NEG_INF, -1).  ``n_valid`` (dynamic scalar,
    default N_db) restricts the search to the first rows of a larger buffer.
    """
    n_db, d = store.shape
    b = queries.shape[0]
    tile, pad_db, pad_b, n_tiles = _grid_geometry(n_db, b, bq, tile)
    queries = _pad_rows(queries, pad_b)
    store = _pad_rows(store, pad_db)
    bp = queries.shape[0]
    nv = jnp.asarray(n_db if n_valid is None else n_valid,
                     jnp.int32).reshape((1,))

    kernel = functools.partial(_topk_kernel, k=k, tile=tile, n_tiles=n_tiles)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(bp // bq, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, d), lambda iq, it: (iq, 0)),
            pl.BlockSpec((tile, d), lambda iq, it: (it, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda iq, it: (iq, 0)),
            pl.BlockSpec((bq, k), lambda iq, it: (iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        interpret=interpret,
    )(nv, queries, store)
    return vals[:b], idx[:b]


def retrieval_vote_kernel(store, labels, queries, k: int, *, bq: int = 128,
                          tile: int = 512, interpret: bool = True,
                          n_valid=None):
    """One launch: sim → top-k → gather-labels → per-model neighbour vote.

    store (N_db, d), labels (N_db, L), queries (B, d).  Returns
    (vals (B, k), idx (B, k), votes (B, L)) where votes are the mean label
    over the *valid* neighbours only (empty slots excluded).
    """
    n_db, d = store.shape
    n_lab = labels.shape[1]
    b = queries.shape[0]
    tile, pad_db, pad_b, n_tiles = _grid_geometry(n_db, b, bq, tile)
    queries = _pad_rows(queries, pad_b)
    store = _pad_rows(store, pad_db)
    labels = _pad_rows(jnp.asarray(labels, jnp.float32), pad_db)
    bp = queries.shape[0]
    nv = jnp.asarray(n_db if n_valid is None else n_valid,
                     jnp.int32).reshape((1,))

    kernel = functools.partial(_vote_kernel, k=k, tile=tile, n_tiles=n_tiles)
    vals, idx, votes = pl.pallas_call(
        kernel,
        grid=(bp // bq, 2, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, d), lambda iq, ph, it: (iq, 0)),
            # phase-aware maps: pin the unused operand to block 0 during the
            # phase that never reads it, so Pallas's unchanged-block
            # revisiting skips the DMA (each buffer streams from HBM ~once
            # per query block, not twice)
            pl.BlockSpec((tile, d), lambda iq, ph, it: (it * (1 - ph), 0)),
            pl.BlockSpec((tile, n_lab), lambda iq, ph, it: (it * ph, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda iq, ph, it: (iq, 0)),
            pl.BlockSpec((bq, k), lambda iq, ph, it: (iq, 0)),
            pl.BlockSpec((bq, n_lab), lambda iq, ph, it: (iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
            jax.ShapeDtypeStruct((bp, n_lab), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
            pltpu.VMEM((bq, n_lab), jnp.float32),
        ],
        interpret=interpret,
    )(nv, queries, store, labels)
    return vals[:b], idx[:b], votes[:b]
