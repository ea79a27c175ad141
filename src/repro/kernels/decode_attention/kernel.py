"""Split-KV flash-decode Pallas kernels: dense and paged.

Dense (``decode_attention_kernel``) — grid (B, K, n_splits). Each split
computes attention of one decode token against its KV slice and emits partial
(o·l, m, l) — the same merge triple the cross-shard ``psum`` combine uses in
the SP-decode path (DESIGN.md §4), so this kernel is both the per-device
decode op and the building block of the sequence-sharded 500k decode.
``pos`` may be a scalar or a per-sequence ``(B,)`` length vector. Ragged
cache lengths (t not a tile multiple) are zero-padded and NEG_INF-masked
in-kernel.

Paged (``paged_decode_attention_kernel``) — the serving-plane variant: the
KV cache is a page pool ``(n_pages, page_size, K, D)`` shared by all
sequences, and each sequence owns a row of a ``block_table (B, P)`` mapping
its logical pages to physical ones.  The block table and the per-sequence
``lens (B,)`` ride scalar prefetch (``pltpu.PrefetchScalarGridSpec``) so the
BlockSpec index map performs the page indirection — no gathered dense copy
of the cache ever materializes.  Grid (B, K, P): split s of sequence b reads
physical page ``block_table[b, s]`` and masks logical positions ≥ lens[b].
ops.py performs the split merge for both variants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _split_partials(q_ref, k_ref, v_ref, on_ref, m_ref, l_ref, *,
                    start, pos, t_valid: int, window: int, scale: float):
    """Shared split body for every variant: the R query rows of each KV head
    (R = G for decode, S·G for verify) against one KV split of BS positions
    starting at logical position ``start``, masked to
    [max(pos - window, 0), min(pos, t_valid)), emitting the (o·l, m, l)
    merge triple per head.

    The K/V block holds ALL K heads of the split, (1, BS, K, D): a TPU block
    must span its array's last two dims (K, D) whole, since K is below the
    8-row sublane tile.  ``pos`` is a scalar or an (R, 1) column."""
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32) * scale      # (R, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)        # (BS, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (R, BS)
        kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # the zero-padded ragged tail (kv_pos >= t_valid) is NEG_INF-masked
        # alongside the not-yet-written region (kv_pos >= pos)
        valid = (kv_pos < pos) & (kv_pos < t_valid)
        if window > 0:
            valid &= kv_pos > pos - 1 - window
        s = jnp.where(valid, s, NEG_INF)
        m = s.max(axis=1, keepdims=True)                 # (R, 1)
        p = jnp.exp(s - m)
        l = p.sum(axis=1, keepdims=True)
        o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (R, D)
        on_ref[0, 0, h] = o.astype(on_ref.dtype)         # o·l numerator
        m_ref[0, 0, h] = m.astype(m_ref.dtype)
        l_ref[0, 0, h] = l.astype(l_ref.dtype)


def _split_call(kernel, args, *, b: int, n_split: int, kh: int, r: int,
                d: int, interpret: bool, **grid_kw):
    """Run a split kernel (``grid_kw``: its grid and block specs) and return
    its partials in the merge layout (o_num (B,K,S,R,D), m (B,K,S,R),
    l (B,K,S,R)).  In-kernel the outputs are split-major, (B, S, K, R, ·),
    so each block spans the (R, D) / (R, 1) tail of its array whole."""
    out_shape = [
        jax.ShapeDtypeStruct((b, n_split, kh, r, d), jnp.float32),
        jax.ShapeDtypeStruct((b, n_split, kh, r, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, n_split, kh, r, 1), jnp.float32),
    ]
    o, m, l = pl.pallas_call(kernel, out_shape=out_shape, interpret=interpret,
                             **grid_kw)(*args)
    o = jnp.swapaxes(o, 1, 2)
    return o, jnp.swapaxes(m[..., 0], 1, 2), jnp.swapaxes(l[..., 0], 1, 2)


def _out_specs(kh: int, r: int, d: int, index):
    return [pl.BlockSpec((1, 1, kh, r, d), index),
            pl.BlockSpec((1, 1, kh, r, 1), index),
            pl.BlockSpec((1, 1, kh, r, 1), index)]


def _kernel(q_ref, k_ref, v_ref, pos_ref, on_ref, m_ref, l_ref, *,
            bs: int, t_valid: int, window: int, scale: float):
    _split_partials(q_ref, k_ref, v_ref, on_ref, m_ref, l_ref,
                    start=pl.program_id(1) * bs,
                    pos=pos_ref[pl.program_id(0)],
                    t_valid=t_valid, window=window, scale=scale)


def decode_attention_kernel(q, k_cache, v_cache, pos, *, window: int = 0,
                            bs: int = 512, interpret: bool = True):
    """q: (B,1,H,D); caches (B,T,K,D); pos scalar or (B,) int32 lengths.

    Returns partials (o_num (B,K,S,G,D), m (B,K,S,G), l (B,K,S,G)) where S is
    the number of KV splits — merged by ops.merge_partials.  T need not be a
    multiple of ``bs``: the ragged tail is zero-padded and masked in-kernel.
    """
    b, _, h, d = q.shape
    t, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    bs = min(bs, t)
    ns = -(-t // bs)                                 # ceil: ragged tail ok
    if ns * bs != t:
        pad = [(0, 0)] * 4
        pad[1] = (0, ns * bs - t)
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)

    qT = q.reshape(b, kh, g, d)                      # (B, K, G, D)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    kernel = functools.partial(_kernel, bs=bs, t_valid=t, window=window,
                               scale=d ** -0.5)
    return _split_call(
        kernel, (qT, k_cache, v_cache, pos_arr), b=b, n_split=ns, kh=kh,
        r=g, d=d, interpret=interpret, grid=(b, ns),
        in_specs=[
            pl.BlockSpec((1, kh, g, d), lambda b_, s_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, bs, kh, d), lambda b_, s_: (b_, s_, 0, 0)),
            pl.BlockSpec((1, bs, kh, d), lambda b_, s_: (b_, s_, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=_out_specs(kh, g, d, lambda b_, s_: (b_, s_, 0, 0, 0)))


def _paged_verify_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, on_ref, m_ref,
                         l_ref, *, ps: int, p_max: int, g: int, window: int,
                         scale: float):
    # the q block folds the S query positions into the row axis (S·G rows);
    # row r belongs to query position r // g, whose valid length is
    # lens[b] + r // g — _split_partials broadcasts the (S·G, 1) column
    # against its (S·G, page) position grid
    rows = jax.lax.broadcasted_iota(jnp.int32, (q_ref.shape[2], 1), 0)
    pos = len_ref[pl.program_id(0)] + rows // g
    _split_partials(q_ref, k_ref, v_ref, on_ref, m_ref, l_ref,
                    start=pl.program_id(1) * ps, pos=pos,
                    t_valid=p_max * ps, window=window, scale=scale)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, on_ref, m_ref, l_ref,
                  *, ps: int, p_max: int, window: int, scale: float):
    # the k/v blocks hold the physical page bt_ref[b, s]; logically it spans
    # positions [s·ps, (s+1)·ps) of sequence b, masked against lens[b]
    _split_partials(q_ref, k_ref, v_ref, on_ref, m_ref, l_ref,
                    start=pl.program_id(1) * ps,
                    pos=len_ref[pl.program_id(0)],
                    t_valid=p_max * ps, window=window, scale=scale)


def _paged_call(kernel, qT, k_pages, v_pages, block_table, lens, *,
                interpret: bool):
    """Shared launch of the paged decode/verify kernels: grid (B, P), the
    block table and lens on scalar prefetch, and page indirection in the
    K/V index maps — the pool is never gathered into a dense copy."""
    b, kh, r, d = qT.shape
    ps = k_pages.shape[1]
    p_max = block_table.shape[1]
    page = lambda b_, s_, bt_, ln_: (bt_[b_, s_], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # (block_table, lens)
        grid=(b, p_max),
        in_specs=[
            pl.BlockSpec((1, kh, r, d), lambda b_, s_, bt_, ln_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, ps, kh, d), page),
            pl.BlockSpec((1, ps, kh, d), page),
        ],
        out_specs=_out_specs(kh, r, d,
                             lambda b_, s_, bt_, ln_: (b_, s_, 0, 0, 0)),
    )
    return _split_call(kernel, (jnp.asarray(block_table, jnp.int32),
                                jnp.asarray(lens, jnp.int32), qT, k_pages,
                                v_pages),
                       b=b, n_split=p_max, kh=kh, r=r, d=d,
                       interpret=interpret, grid_spec=grid_spec)


def paged_verify_attention_kernel(q, k_pages, v_pages, block_table, lens, *,
                                  window: int = 0, interpret: bool = True):
    """Speculative-verify twin of ``paged_decode_attention_kernel``:
    q is (B,S,H,D) — S query positions per sequence, query s of sequence b
    masked to positions < lens[b] + s.  The S axis rides the q block's row
    axis (S·G rows per KV head), so the grid and the block-table
    scalar-prefetch indirection are identical to the decode kernel.

    Returns partials (o_num (B,K,P,S·G,D), m (B,K,P,S·G), l (B,K,P,S·G)).
    """
    b, s_q, h, d = q.shape
    ps, kh = k_pages.shape[1], k_pages.shape[2]
    g = h // kh
    p_max = block_table.shape[1]

    # (B,S,H,D) -> (B, K, S·G, D): row r of head k is query position r // g,
    # query-group r % g
    qT = q.reshape(b, s_q, kh, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kh, s_q * g, d)
    kernel = functools.partial(_paged_verify_kernel, ps=ps, p_max=p_max,
                               g=g, window=window, scale=d ** -0.5)
    return _paged_call(kernel, qT, k_pages, v_pages, block_table, lens,
                       interpret=interpret)


def paged_decode_attention_kernel(q, k_pages, v_pages, block_table, lens, *,
                                  window: int = 0, interpret: bool = True):
    """q: (B,1,H,D); pools (n_pages, PS, K, D); block_table (B, P) int32
    physical page ids; lens (B,) int32 valid lengths.

    Returns partials (o_num (B,K,P,G,D), m (B,K,P,G), l (B,K,P,G)) — one
    split per logical page, merged by ops.merge_partials.  Pages past a
    sequence's length are fully masked (m = NEG_INF) and vanish in the merge,
    so every sequence may use any subset of its block-table row.
    """
    b, _, h, d = q.shape
    ps, kh = k_pages.shape[1], k_pages.shape[2]
    p_max = block_table.shape[1]
    kernel = functools.partial(_paged_kernel, ps=ps, p_max=p_max,
                               window=window, scale=d ** -0.5)
    return _paged_call(kernel, q.reshape(b, kh, h // kh, d), k_pages, v_pages,
                       block_table, lens, interpret=interpret)
