"""Attention: online-softmax (flash-style) prefill/train path in pure jnp, and
masked-softmax decode path over a (possibly sequence-sharded) KV cache.

On TPU the Pallas kernels in ``repro.kernels`` replace these bodies
(``cfg.use_pallas``); the jnp path is the XLA-lowerable reference used by the
CPU dry-run and the kernels' oracle.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common import ParamDecl, logical_shard
from repro.configs.base import ModelConfig
from .layers import rope

NEG_INF = -1e30


def attn_decls(cfg: ModelConfig, *, cross: bool = False) -> dict:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    decls = {
        "wq": ParamDecl((d, h, hd), ("p_embed", "p_heads", "p_none"), init="scaled"),
        "wk": ParamDecl((d, k, hd), ("p_embed", "p_kv_heads", "p_none"), init="scaled"),
        "wv": ParamDecl((d, k, hd), ("p_embed", "p_kv_heads", "p_none"), init="scaled"),
        "wo": ParamDecl((h, hd, d), ("p_heads", "p_none", "p_embed"), init="scaled"),
    }
    if cfg.qkv_bias:
        decls["bq"] = ParamDecl((h, hd), ("p_heads", "p_none"), init="zeros")
        decls["bk"] = ParamDecl((k, hd), ("p_kv_heads", "p_none"), init="zeros")
        decls["bv"] = ParamDecl((k, hd), ("p_kv_heads", "p_none"), init="zeros")
    return decls


def _pos2d(pos, s: int) -> jax.Array:
    """Decode positions as a 2-D (batch-broadcastable, S) array.

    ``pos`` scalar -> (1, S) shared by the batch; ``pos`` (B,) per-sequence
    lengths -> (B, S) — the paged serving plane decodes ragged batches where
    every sequence sits at its own position.
    """
    pos = jnp.asarray(pos, jnp.int32)
    base = jnp.arange(s, dtype=jnp.int32)
    if pos.ndim == 0:
        return (pos + base)[None, :]
    return pos[:, None] + base[None, :]


def _mask(q_pos, kv_pos, *, causal: bool, window: int) -> jax.Array:
    """(..., Sq, Skv) boolean validity mask from absolute positions."""
    m = jnp.ones((q_pos.shape[-1], kv_pos.shape[-1]), bool)
    if causal:
        m = m & (kv_pos[None, :] <= q_pos[:, None])
    if window > 0:
        m = m & (kv_pos[None, :] > q_pos[:, None] - window)
    return m


def flash_attention_jnp(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, K, D)
    v: jax.Array,  # (B, Skv, K, D)
    *,
    causal: bool,
    window: int = 0,
    q_offset: int = 0,
    kv_chunk: int = 512,
) -> jax.Array:
    """Online-softmax attention scanning over KV chunks (O(S) memory)."""
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh
    scale = d ** -0.5

    kv_chunk = min(kv_chunk, skv)
    if skv % kv_chunk:
        # fall back to the largest divisor instead of crashing on ragged
        # lengths (SC05); online softmax is exact for any chunk size
        kv_chunk = math.gcd(skv, kv_chunk)
    n = skv // kv_chunk

    # bf16 operands + fp32 accumulation (preferred_element_type): no full-array
    # fp32 casts ever materialize (MXU-native mixed precision)
    qf = q.reshape(b, sq, kh, g, d) * jnp.asarray(scale, q.dtype)
    kc = k.reshape(b, n, kv_chunk, kh, d)
    vc = v.reshape(b, n, kv_chunk, kh, d)
    kc = jnp.moveaxis(kc, 1, 0)  # (n, B, C, K, D)
    vc = jnp.moveaxis(vc, 1, 0)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, inp):
        m_run, l_run, acc = carry
        kx, vx, start = inp
        kv_pos = start + jnp.arange(kv_chunk)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf, kx,
                       preferred_element_type=jnp.float32)
        valid = _mask(q_pos, kv_pos, causal=causal, window=window)
        s = jnp.where(valid[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(q.dtype), vx,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, sq, kh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kh, g), jnp.float32)
    a0 = jnp.zeros((b, sq, kh, g, d), jnp.float32)
    starts = jnp.arange(n) * kv_chunk
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, starts))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return out.reshape(b, sq, h, d).astype(q.dtype)


def decode_attention_jnp(
    q: jax.Array,        # (B, 1, H, D)
    k_cache: jax.Array,  # (B, T, K, D)   (possibly seq-sharded over 'model')
    v_cache: jax.Array,  # (B, T, K, D)
    pos: jax.Array,      # scalar int32 — or (B,) per-sequence valid lengths
    *,
    window: int = 0,
) -> jax.Array:
    b, _, h, d = q.shape
    t, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = d ** -0.5
    qf = q.reshape(b, kh, g, d) * jnp.asarray(scale, q.dtype)
    # bf16 x bf16 -> fp32 accumulation: never materializes an fp32 cache copy
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k_cache,
                   preferred_element_type=jnp.float32)
    kv_pos = jnp.arange(t)
    pcol = jnp.asarray(pos, jnp.int32).reshape(-1, 1)  # (B or 1, 1)
    valid = kv_pos[None, :] < pcol
    if window > 0:
        valid = valid & (kv_pos[None, :] > pcol - 1 - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    # softmax over (possibly sharded) T: GSPMD turns max/sum into psums
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgt,btkd->bkgd", (p / jnp.maximum(l, 1e-30)).astype(q.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def verify_attention_jnp(
    q: jax.Array,        # (B, S, H, D) — S prewritten query positions
    k_cache: jax.Array,  # (B, T, K, D)
    v_cache: jax.Array,  # (B, T, K, D)
    pos: jax.Array,      # scalar int32 — or (B,) valid lengths of query 0
    *,
    window: int = 0,
) -> jax.Array:
    """Speculative-verify twin of ``decode_attention_jnp``: query position s
    of sequence b is masked to cache positions < pos[b] + s.  Every op is the
    S-batched form of the decode body (same operand dtypes, same fp32
    accumulation), so each S-slice is bit-identical to the sequential decode
    step at the same position — the property the acceptance loop's
    bit-exactness guarantee rests on."""
    b, s_q, h, d = q.shape
    t, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = d ** -0.5
    qf = q.reshape(b, s_q, kh, g, d) * jnp.asarray(scale, q.dtype)
    s = jnp.einsum("bskgd,btkd->bskgt", qf, k_cache,
                   preferred_element_type=jnp.float32)
    kv_pos = jnp.arange(t)
    # per-position valid lengths (B or 1, S, 1)
    pcol = (jnp.asarray(pos, jnp.int32).reshape(-1, 1)
            + jnp.arange(s_q, dtype=jnp.int32)[None, :])[:, :, None]
    valid = kv_pos[None, None, :] < pcol
    if window > 0:
        valid = valid & (kv_pos[None, None, :] > pcol - 1 - window)
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bskgt,btkd->bskgd",
                     (p / jnp.maximum(l, 1e-30)).astype(q.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, s_q, h, d).astype(q.dtype)


def project_kv_token(cfg: ModelConfig, params: dict, x: jax.Array, pos,
                     use_rope: bool = True):
    """K/V projection (+RoPE at pos) for one decode token. x: (B,1,d);
    pos scalar or per-sequence (B,)."""
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bk" in params:
        k_new, v_new = k_new + params["bk"], v_new + params["bv"]
    if use_rope:
        k_new = rope(k_new, _pos2d(pos, x.shape[1]), cfg.rope_theta)
    return k_new, v_new


def attention_block(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,                     # (B, Sq, d)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset=0,
    kv_x: Optional[jax.Array] = None,  # cross-attention source (B, Skv, d)
    cache: Optional[dict] = None,      # {'k','v'} (B,T,K,D) + 'pos' for decode
    use_rope: bool = True,
    cross_cached: bool = False,        # decode vs a static (encoder) KV cache
    prewritten: bool = False,          # decode: cache already holds this token
):
    """Full attention block: projections + rope + core + output projection.

    Returns (out, new_kv) where new_kv is (k, v) of this call (for cache build)
    or None for cross-attention reuse.
    """
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    decode = cache is not None

    if decode and cross_cached:  # static memory (encoder output) KV
        out = decode_attention_jnp(q, cache["k"], cache["v"],
                                   jnp.asarray(cache["k"].shape[1]), window=0)
        new_kv = None
    elif decode and prewritten:
        # cache already contains this token's K/V at position pos (written
        # into the stacked carry buffer — or page pool — by the caller; one
        # token column only).  pos may be per-sequence (B,) lengths.
        pos = cache["pos"]
        sq = x.shape[1]
        if use_rope:
            q = rope(q, _pos2d(pos, sq), cfg.rope_theta)
        q = logical_shard(q, "batch", None, None, None)  # gather q heads
        if "k_pages" in cache:  # paged serving plane: block-table indirection
            if sq > 1:
                # speculative verify: S prewritten positions per sequence,
                # one multi-position pass
                if cfg.use_pallas:
                    from repro.kernels.decode_attention.ops import paged_verify_attention
                    out = paged_verify_attention(
                        q, cache["k_pages"], cache["v_pages"],
                        cache["block_table"], jnp.asarray(pos, jnp.int32) + 1,
                        window=window)
                else:
                    from repro.kernels.decode_attention.ref import gather_pages
                    out = verify_attention_jnp(
                        q, gather_pages(cache["k_pages"], cache["block_table"]),
                        gather_pages(cache["v_pages"], cache["block_table"]),
                        jnp.asarray(pos, jnp.int32) + 1, window=window)
            elif cfg.use_pallas:
                from repro.kernels.decode_attention.ops import paged_decode_attention
                out = paged_decode_attention(
                    q, cache["k_pages"], cache["v_pages"], cache["block_table"],
                    jnp.asarray(pos, jnp.int32) + 1, window=window)
            else:
                # XLA path: gather the block-table pages and run the SAME
                # mixed-precision body as the dense decode path (bf16
                # operands, fp32 accumulation) — numerics must not depend on
                # the cache layout
                from repro.kernels.decode_attention.ref import gather_pages
                with jax.named_scope("gather"):
                    kd = gather_pages(cache["k_pages"], cache["block_table"])
                    vd = gather_pages(cache["v_pages"], cache["block_table"])
                out = decode_attention_jnp(q, kd, vd,
                                           jnp.asarray(pos, jnp.int32) + 1,
                                           window=window)
        elif sq > 1:
            out = verify_attention_jnp(q, cache["k"], cache["v"], pos + 1,
                                       window=window)
        else:
            out = decode_attention_jnp(q, cache["k"], cache["v"], pos + 1,
                                       window=window)
        new_kv = None
    elif decode and kv_x is None:
        k_new = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", src, params["wv"])
        if "bk" in params:
            k_new, v_new = k_new + params["bk"], v_new + params["bv"]
        pos = cache["pos"]
        if use_rope:
            q = rope(q, pos + jnp.zeros((x.shape[1],), jnp.int32)[None, :], cfg.rope_theta)
            k_new = rope(k_new, pos + jnp.zeros((x.shape[1],), jnp.int32)[None, :], cfg.rope_theta)
        k_c = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                           (0, pos, 0, 0))
        v_c = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                           (0, pos, 0, 0))
        k_c = logical_shard(k_c, "cache_batch", "cache_seq", "cache_kv_heads", None)
        v_c = logical_shard(v_c, "cache_batch", "cache_seq", "cache_kv_heads", None)
        q = logical_shard(q, "batch", None, None, None)  # gather q heads
        out = decode_attention_jnp(q, k_c, v_c, pos + 1, window=window)
        new_kv = (k_c, v_c)
    else:
        k = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", src, params["wv"])
        if "bk" in params:
            k, v = k + params["bk"], v + params["bv"]
        if use_rope:
            q_pos = q_offset + jnp.arange(x.shape[1])
            kv_pos = jnp.arange(src.shape[1])
            q = rope(q, q_pos[None, :], cfg.rope_theta)
            k = rope(k, kv_pos[None, :], cfg.rope_theta)
        q = logical_shard(q, "batch", "qseq", "heads", None)
        k = logical_shard(k, "batch", None, "kv_heads", None)
        v = logical_shard(v, "batch", None, "kv_heads", None)
        out = flash_attention_jnp(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset if isinstance(q_offset, int) else 0)
        out = logical_shard(out, "batch", "qseq", "heads", None)
        new_kv = (k, v)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return logical_shard(y, "batch", "seq", "embed"), new_kv
