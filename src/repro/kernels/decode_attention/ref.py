"""Oracles for split-KV flash-decode: dense and paged.

House kernel pattern: the jnp references are the XLA-lowerable off-TPU
fallbacks (ops.py dispatches to them by backend) and the NumPy references are
the test oracles — a plain per-sequence softmax loop with no shared code
with either device path.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def _lens_col(pos):
    """pos scalar or (B,) -> (B or 1, 1) column for broadcast masking."""
    return jnp.asarray(pos, jnp.int32).reshape(-1, 1)


def decode_attention_ref(q, k_cache, v_cache, pos, *, window: int = 0):
    """q: (B,1,H,D); caches (B,T,K,D); pos: scalar or per-sequence (B,)
    valid lengths. fp32 softmax."""
    b, _, h, d = q.shape
    t, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    qf = q.reshape(b, kh, g, d).astype(jnp.float32) * (d ** -0.5)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k_cache.astype(jnp.float32))
    kv = jnp.arange(t)
    pcol = _lens_col(pos)                             # (B or 1, 1)
    valid = kv[None, :] < pcol
    if window > 0:
        valid = valid & (kv[None, :] > pcol - 1 - window)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, d).astype(q.dtype)


def gather_pages(k_pages, block_table):
    """(n_pages, PS, K, D) + (B, P) -> dense (B, P·PS, K, D) view."""
    b, p = block_table.shape
    ps, kh, d = k_pages.shape[1:]
    return jnp.take(k_pages, block_table, axis=0).reshape(b, p * ps, kh, d)


def paged_decode_attention_ref(q, k_pages, v_pages, block_table, lens, *,
                               window: int = 0):
    """jnp reference (and off-TPU fallback): gather the block-table pages
    into a dense per-sequence view, then lens-masked split-free softmax."""
    return decode_attention_ref(q, gather_pages(k_pages, block_table),
                                gather_pages(v_pages, block_table),
                                lens, window=window)


def verify_attention_ref(q, k_cache, v_cache, lens, *, window: int = 0):
    """Speculative-verify reference: q is (B,S,H,D) — S query positions per
    sequence, where query s of sequence b sits at cache position
    ``lens[b] - 1 + s`` and attends to positions < ``lens[b] + s`` (its own
    K/V is already written, exactly like the decode path's ``pos + 1``
    convention).  fp32 softmax.

    Built as S decode references, one per position: slice s IS
    ``decode_attention_ref`` at ``lens + s``, bit for bit, whatever
    reduction order the backend picks for an S-batched einsum."""
    lens = jnp.asarray(lens, jnp.int32)
    return jnp.concatenate(
        [decode_attention_ref(q[:, j:j + 1], k_cache, v_cache, lens + j,
                              window=window) for j in range(q.shape[1])],
        axis=1)


def paged_verify_attention_ref(q, k_pages, v_pages, block_table, lens, *,
                               window: int = 0):
    """jnp reference (and off-TPU fallback) for the paged verify step:
    gather the block-table pages into a dense view, then the per-position
    causal mask of ``verify_attention_ref``."""
    return verify_attention_ref(q, gather_pages(k_pages, block_table),
                                gather_pages(v_pages, block_table),
                                lens, window=window)


def paged_verify_attention_np(q, k_pages, v_pages, block_table, lens, *,
                              window: int = 0):
    """NumPy oracle for the paged verify step: a per-(sequence, position)
    python loop — query s of sequence b sees positions [lo, lens[b] + s)."""
    in_dtype = np.asarray(q).dtype
    q = np.asarray(q, np.float32)
    k_pages = np.asarray(k_pages, np.float32)
    v_pages = np.asarray(v_pages, np.float32)
    block_table = np.asarray(block_table)
    lens = np.asarray(lens)
    b, s_q, h, d = q.shape
    ps, kh = k_pages.shape[1], k_pages.shape[2]
    g = h // kh
    out = np.zeros((b, s_q, h, d), np.float32)
    for i in range(b):
        pages = block_table[i]
        kd = k_pages[pages].reshape(-1, kh, d)
        vd = v_pages[pages].reshape(-1, kh, d)
        for j in range(s_q):
            n = int(lens[i]) + j
            lo = max(0, n - window) if window > 0 else 0
            if n - lo <= 0:
                continue
            k = kd[lo:n]
            v = vd[lo:n]
            qi = q[i, j].reshape(kh, g, d) * (d ** -0.5)
            s = np.einsum("kgd,tkd->kgt", qi, k)
            s = s - s.max(-1, keepdims=True)
            p = np.exp(s)
            p = p / p.sum(-1, keepdims=True)
            out[i, j] = np.einsum("kgt,tkd->kgd", p, v).reshape(h, d)
    return out.astype(in_dtype)


def paged_decode_attention_np(q, k_pages, v_pages, block_table, lens, *,
                              window: int = 0):
    """NumPy oracle: per-sequence python loop, no masking tricks — the
    ground truth both device paths must match."""
    in_dtype = np.asarray(q).dtype
    q = np.asarray(q, np.float32)
    k_pages = np.asarray(k_pages, np.float32)
    v_pages = np.asarray(v_pages, np.float32)
    block_table = np.asarray(block_table)
    lens = np.asarray(lens)
    b, _, h, d = q.shape
    ps, kh = k_pages.shape[1], k_pages.shape[2]
    g = h // kh
    out = np.zeros((b, 1, h, d), np.float32)
    for i in range(b):
        n = int(lens[i])
        lo = max(0, n - window) if window > 0 else 0
        if n - lo <= 0:
            continue
        pages = block_table[i]
        k = k_pages[pages].reshape(-1, kh, d)[lo:n]   # (n-lo, K, D)
        v = v_pages[pages].reshape(-1, kh, d)[lo:n]
        qi = q[i, 0].reshape(kh, g, d) * (d ** -0.5)
        s = np.einsum("kgd,tkd->kgt", qi, k)
        s = s - s.max(-1, keepdims=True)
        p = np.exp(s)
        p = p / p.sum(-1, keepdims=True)
        out[i, 0] = np.einsum("kgt,tkd->kgd", p, v).reshape(h, d)
    return out.astype(in_dtype)
