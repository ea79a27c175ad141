"""Split-KV flash-decode Pallas kernels: dense and paged.

Dense (``decode_attention_kernel``) — grid (B, K, n_splits). Each split
computes attention of one decode token against its KV slice and emits partial
(o·l, m, l) — the same merge triple the cross-shard ``psum`` combine uses in
the SP-decode path (DESIGN.md §4), so this kernel is both the per-device
decode op and the building block of the sequence-sharded 500k decode.
``pos`` may be a scalar or a per-sequence ``(B,)`` length vector. Ragged
cache lengths (t not a tile multiple) are zero-padded and NEG_INF-masked
in-kernel.

Paged (``paged_decode_attention_kernel``, ``paged_verify_attention_kernel``)
— the serving-plane variant: the KV cache is a stacked page pool ``(L,
n_pages, page_size, K, D)`` shared by all sequences, each sequence owns a
row of a ``block_table (B, P)`` mapping its logical pages to physical ones,
and ``layer`` picks the pool's layer.  Grid (B, blocks of several pages):
each live page comes straight from ``pool[layer, page]`` into VMEM, blocks
past a sequence's length (or before its window) are neither copied nor
computed, the online softmax stays in VMEM scratch, and each sequence's
output is written once.  Nothing is merged outside the kernel.  A page
(PS, K, D) is read as PS·K rows of D, so one product scores every KV head
of a block at once; a mask keeps each query row to its own head's rows.
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
    """Split body of the dense kernel: the G query rows of each KV head
    against one KV split of BS positions starting at logical position
    ``start``, masked to [max(pos - window, 0), min(pos, t_valid)),
    emitting the (o·l, m, l) merge triple per head.

    The K/V block holds ALL K heads of the split, (1, BS, K, D): a TPU block
    must span its array's last two dims (K, D) whole, since K is below the
    8-row sublane tile.  ``pos`` is the sequence's length, a scalar."""
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


# Positions of one block of the paged kernel: on a TPU v5e, blocks of 64,
# 128 and 256 positions time 0.218 / 0.198 / 0.186 ms a layer at
# h2o-danube3-4b's decode shapes and 0.471 / 0.452 / 0.489 at
# internlm2-20b's (PERF.md §6).
BLOCK_POSITIONS = 128


def pages_per_block(page_size: int, pages_per_slot: int) -> int:
    """Pages of one block of the paged kernel, from the pool's shapes: as
    many as make ``BLOCK_POSITIONS`` positions, and no more than a slot."""
    return max(1, min(pages_per_slot, BLOCK_POSITIONS // page_size))


def _span(lens, s_q: int, window: int):
    """[lo, hi): the positions some query row of a sequence attends to.
    Row r sits at ``lens - 1 + r // G`` and sees positions below
    ``lens + r // G``, and with a window none below ``lens + r // G -
    window``."""
    hi = lens + s_q - 1
    lo = jnp.maximum(lens - window, 0) if window > 0 else jnp.zeros_like(lens)
    return lo, hi


def _blocks(lo, hi, bk: int):
    """First and last block a sequence computes: those that meet [lo, hi),
    and block ``lo // bk`` alone where the span is empty."""
    first = lo // bk
    return first, jnp.maximum((hi + bk - 1) // bk - 1, first)


def _page_schedule(block_table, lens, *, ps: int, ppb: int, s_q: int,
                   window: int):
    """The physical page each of a block's ``ppb`` page operands holds at
    each grid step (b, j), flattened: page ``j·ppb + p`` of sequence b
    where that page meets the sequence's span, and otherwise the page the
    operand held at the step before, so the pipeline copies nothing for
    it.  Steps outside a sequence's blocks repeat its nearest block."""
    b, p_max = block_table.shape
    nb = -(-p_max // ppb)
    lo, hi = _span(lens, s_q, window)
    first, last = _blocks(lo, hi, ppb * ps)
    jj = jnp.clip(jnp.arange(nb)[None, :], first[:, None], last[:, None])
    pg = jj[..., None] * ppb + jnp.arange(ppb)                # (B, NB, ppb)
    live = (pg * ps < hi[:, None, None]) & ((pg + 1) * ps > lo[:, None, None])
    phys = jnp.take_along_axis(block_table, jnp.minimum(pg, p_max - 1)
                               .reshape(b, nb * ppb), axis=1)
    phys = phys.reshape(b * nb, ppb)
    step = jnp.arange(b * nb, dtype=jnp.int32)[:, None]
    held = jax.lax.cummax(jnp.where(live.reshape(b * nb, ppb), step, -1),
                          axis=0)
    return jnp.where(held >= 0, jnp.take_along_axis(
        phys, jnp.maximum(held, 0), axis=0), 0).reshape(-1)


def _paged_kernel(layer_ref, sched_ref, len_ref, rh_ref, ro_ref, ct_ref,
                  ch_ref, q_ref, *refs, ps: int, ppb: int, s_q: int,
                  window: int):
    """One (sequence b, block j) step: the ``ppb`` K and V pages of the
    block (``refs``: K pages, V pages, then the output and the running
    max, sum and output), already in VMEM, each read as (PS·K, D) rows,
    position-major, against all K·R query rows in one product.  A score
    counts where the column's KV head is the row's (``ch_ref`` against
    ``rh_ref``) and the column's position (``ct_ref``, within the block)
    lies in the row's span (row r of a head sits ``ro_ref`` positions past
    ``lens - 1``, as ``_span`` says); blocks outside the sequence's span do
    nothing."""
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * ppb:]
    b, j = pl.program_id(0), pl.program_id(1)
    bk = ppb * ps
    n = len_ref[b]
    first, last = _blocks(*_span(n, s_q, window), bk)

    @pl.when(j == first)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((j >= first) & (j <= last))
    def _():
        kh, d = k_refs[0].shape[1:]
        k = jnp.concatenate([kr[...].reshape(ps * kh, d) for kr in k_refs],
                            axis=0)                       # (BK·K, D)
        v = jnp.concatenate([vr[...].reshape(ps * kh, d) for vr in v_refs],
                            axis=0)
        s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kv_pos = j * bk + ct_ref[...]                     # (1, BK·K)
        pos = n + ro_ref[...]                             # (K·R, 1)
        valid = (ch_ref[...] == rh_ref[...]) & (kv_pos < pos)
        if window > 0:
            valid &= kv_pos >= pos - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # a masked weight is exactly 0, so a block that is masked for a row
        # leaves that row's running state exactly as it was
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == last)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _paged_call(qT, k_pool, v_pool, block_table, lens, layer, *, s_q: int,
                g: int, window: int, interpret: bool):
    """Shared launch of the paged decode and verify kernels.

    Grid (B, blocks): the sequence axis is parallel; the block axis walks a
    sequence's blocks of ``pages_per_block`` pages in order and carries the
    online softmax in VMEM scratch.  Each page of a block is an operand of
    its own whose index map reads ``pool[layer, page]`` for the page the
    schedule names, so the pipeline copies each live page straight from
    the stacked pool in HBM, the next step's while this one computes, and
    copies nothing where the schedule repeats a page.  The layer index, the
    schedule and ``lens`` ride scalar prefetch; the row and column indices
    of the head mask are constant blocks, copied once.  ``qT`` is (B, K,
    R, D), already scaled; returns (B, K, R, D)."""
    b, kh, r, d = qT.shape
    ps = k_pool.shape[2]
    p_max = block_table.shape[1]
    ppb = pages_per_block(ps, p_max)
    nb = -(-p_max // ppb)
    sched = _page_schedule(jnp.asarray(block_table, jnp.int32),
                           jnp.asarray(lens, jnp.int32), ps=ps, ppb=ppb,
                           s_q=s_q, window=window)
    rows = jnp.arange(kh * r, dtype=jnp.int32)[:, None]
    cols = jnp.arange(ppb * ps * kh, dtype=jnp.int32)[None, :]
    const = lambda a: pl.BlockSpec(a.shape, lambda *_: (0, 0))
    aux = (rows // r, (rows % r) // g, cols // kh, cols % kh)
    row = lambda b_, j_, *_: (b_, 0, 0)

    def page(p):
        return pl.BlockSpec(
            (None, None, ps, kh, d),
            lambda b_, j_, layer_, sched_, lens_:
                (layer_[0], sched_[(b_ * nb + j_) * ppb + p], 0, 0, 0))

    kernel = functools.partial(_paged_kernel, ps=ps, ppb=ppb, s_q=s_q,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,               # (layer, schedule, lens)
        grid=(b, nb),
        in_specs=[const(a) for a in aux]
        + [pl.BlockSpec((1, kh * r, d), row)]
        + [page(p) for p in range(ppb)] * 2,
        out_specs=pl.BlockSpec((1, kh * r, d), row),
        scratch_shapes=[pltpu.VMEM((kh * r, 1), jnp.float32),
                        pltpu.VMEM((kh * r, 1), jnp.float32),
                        pltpu.VMEM((kh * r, d), jnp.float32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh * r, d), qT.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), sched,
      jnp.asarray(lens, jnp.int32), *aux, qT.reshape(b, kh * r, d),
      *[k_pool] * ppb, *[v_pool] * ppb)
    return out.reshape(b, kh, r, d)


def _scaled(q, kh: int):
    """(B, S, H, D) -> (B, K, S·G, D), scaled by D^-0.5 in q's dtype (the
    jnp decode body's operand): row r of head k is query position r // G,
    query group r % G."""
    b, s_q, h, d = q.shape
    q = q * jnp.asarray(d ** -0.5, q.dtype)
    return q.reshape(b, s_q, kh, h // kh, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kh, s_q * (h // kh), d)


def paged_verify_attention_kernel(q, k_pool, v_pool, block_table, lens, layer,
                                  *, window: int = 0, interpret: bool = True):
    """Speculative-verify twin of ``paged_decode_attention_kernel``: q is
    (B,S,H,D), query s of sequence b attends to positions < lens[b] + s.
    The S axis rides the query rows (S·G per KV head) of the same launch
    and body, so slice s equals the decode kernel at ``lens + s`` bit for
    bit: blocks and pages beyond a row's span are masked for it, and a
    masked block leaves its running state exactly as it was.
    Returns (B,S,H,D)."""
    b, s_q, h, d = q.shape
    kh = k_pool.shape[3]
    g = h // kh
    out = _paged_call(_scaled(q, kh), k_pool, v_pool, block_table, lens,
                      layer, s_q=s_q, g=g, window=window, interpret=interpret)
    return out.reshape(b, kh, s_q, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, s_q, h, d)


def paged_decode_attention_kernel(q, k_pool, v_pool, block_table, lens, layer,
                                  *, window: int = 0, interpret: bool = True):
    """q: (B,1,H,D); stacked pools (L, n_pages, PS, K, D); block_table (B, P)
    int32 physical page ids; lens (B,) int32 valid lengths; ``layer`` the
    (traced) layer of the pools to read.  Returns (B,1,H,D) in q's dtype.

    Reads only the pages that hold positions below ``lens[b]`` (and, with a
    window, at or above ``lens[b] - window``), in place: no layer slice and
    no gathered view of the pool is made.  A sequence with ``lens`` 0 reads
    nothing and returns zeros."""
    return paged_verify_attention_kernel(q, k_pool, v_pool, block_table, lens,
                                         layer, window=window,
                                         interpret=interpret)
