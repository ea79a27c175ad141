"""Decoder LM covering the dense / MoE / hybrid / xLSTM families.

The layer stack is grouped into scannable segments (``plan.layer_plan``); each
segment runs as one ``lax.scan`` over stacked parameters, keeping HLO size
independent of depth. The same code path serves training (full-sequence),
prefill (returns KV/recurrent caches) and single-token decode.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import ParamDecl, init_params, is_decl, logical_shard
from repro.configs.base import ModelConfig
from .attention import attn_decls, attention_block, project_kv_token
from .hymba_block import hymba_decls, hymba_layer
from .layers import (chunked_softmax_xent, embed_decls, embed_lookup, mlp,
                     mlp_decls, norm_decl, rms_norm)
from .moe import moe_block, moe_decls
from .plan import LayerKind, layer_plan
from .xlstm_blocks import (mlstm_block, mlstm_decls, slstm_block, slstm_decls,
                           _dims as xlstm_dims)


def _stack(decls, count: int):
    return jax.tree.map(
        lambda d: ParamDecl((count,) + d.shape, ("p_layers",) + d.logical,
                            d.init, d.scale, d.dtype),
        decls, is_leaf=is_decl,
    )


def _layer_decls(cfg: ModelConfig, kind: LayerKind) -> dict:
    if kind.block == "mlstm":
        return {"mlstm": mlstm_decls(cfg)}
    if kind.block == "slstm":
        return {"slstm": slstm_decls(cfg)}
    if kind.block == "hymba":
        return {
            "hymba": hymba_decls(cfg),
            "ln2": norm_decl(cfg.d_model),
            "ffn": mlp_decls(cfg.d_model, cfg.d_ff),
        }
    d = {
        "ln1": norm_decl(cfg.d_model),
        "attn": attn_decls(cfg),
        "ln2": norm_decl(cfg.d_model),
    }
    if kind.is_moe:
        d["ffn"] = moe_decls(cfg)
    else:
        ff = cfg.dense_d_ff or cfg.d_ff
        d["ffn"] = mlp_decls(cfg.d_model, ff)
    if kind.block == "xdec":
        d["ln_cross"] = norm_decl(cfg.d_model)
        d["cross"] = attn_decls(cfg)
    return d


def _empty_cache_for(cfg: ModelConfig, kind: LayerKind, batch: int, t_max: int,
                     dtype) -> Dict[str, Any]:
    """Per-layer cache/state buffers (ShapeDtype-compatible zeros)."""
    out: Dict[str, Any] = {}
    if kind.block in ("attn", "xdec", "hymba"):
        k, hd = cfg.n_kv_heads, cfg.hd
        int8 = cfg.kv_cache_dtype == "int8" and kind.block == "attn"
        cdt = jnp.int8 if int8 else dtype
        out["k"] = jnp.zeros((batch, t_max, k, hd), cdt)
        out["v"] = jnp.zeros((batch, t_max, k, hd), cdt)
        if int8:
            out["k_scale"] = jnp.zeros((batch, t_max, k), jnp.float32)
            out["v_scale"] = jnp.zeros((batch, t_max, k), jnp.float32)
    if kind.block == "xdec":
        out["ck"] = None  # filled at prefill with encoder memory KV
        out["cv"] = None
    if kind.block == "hymba":
        h, p, n = cfg.n_heads, cfg.hd, cfg.ssm_state
        out["s"] = jnp.zeros((batch, h, n, p), jnp.float32)
        out["conv"] = jnp.zeros((batch, cfg.ssm_conv - 1, h * p), dtype)
    if kind.block == "mlstm":
        d, d_inner, h, dk, dv = xlstm_dims(cfg)
        out["s"] = jnp.zeros((batch, h, dk, dv + 1), jnp.float32)
        out["conv"] = jnp.zeros((batch, cfg.ssm_conv - 1, d_inner), dtype)
    if kind.block == "slstm":
        h = cfg.n_heads
        dh = cfg.d_model // h
        for f in ("c", "n", "h"):
            out[f] = jnp.zeros((batch, h, dh), jnp.float32)
    return out


def _ffn_residual(cfg: ModelConfig, kind: LayerKind, params: dict,
                  x: jax.Array) -> jax.Array:
    """Shared post-attention tail: ln2 + (MoE or dense) FFN residual — one
    definition so the full-sequence, dense-decode and paged-decode paths
    cannot drift apart."""
    with jax.named_scope("mlp"):
        f = rms_norm(x, params["ln2"], cfg.norm_eps)
        if kind.is_moe:
            return x + moe_block(cfg, params["ffn"], f)
        return x + mlp(params["ffn"], f)


def _apply_layer(cfg: ModelConfig, kind: LayerKind, params: dict, x: jax.Array,
                 *, q_offset=0, cache: Optional[dict] = None,
                 enc_memory: Optional[jax.Array] = None):
    """Returns (x, new_cache_or_None)."""
    new_cache: Dict[str, Any] = {}
    if kind.block in ("attn", "enc", "xdec"):
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        attn_cache = None
        if cache is not None:
            attn_cache = {"k": cache["k"], "v": cache["v"], "pos": cache["pos"]}
        with jax.named_scope("attention"):
            a, kv = attention_block(
                cfg, params["attn"], h, causal=(kind.block != "enc"),
                window=kind.window, q_offset=q_offset, cache=attn_cache,
            )
        x = x + a
        if kv is not None:
            if cfg.kv_cache_dtype == "int8" and kind.block == "attn":
                new_cache["k"], new_cache["k_scale"] = _quant_kv(kv[0])
                new_cache["v"], new_cache["v_scale"] = _quant_kv(kv[1])
            else:
                new_cache["k"], new_cache["v"] = kv
        if kind.block == "xdec":
            h = rms_norm(x, params["ln_cross"], cfg.norm_eps)
            if cache is not None:  # decode: reuse cached encoder KV
                ca, _ = attention_block(
                    cfg, params["cross"], h, causal=False, use_rope=False,
                    cache={"k": cache["ck"], "v": cache["cv"],
                           "pos": cache["pos"]},
                    kv_x=None, cross_cached=True,
                )
            else:
                ca, ckv = attention_block(
                    cfg, params["cross"], h, causal=False, use_rope=False,
                    kv_x=enc_memory,
                )
                new_cache["ck"], new_cache["cv"] = ckv
            x = x + ca
        return _ffn_residual(cfg, kind, params, x), new_cache
    if kind.block == "hymba":
        hc = cache
        out, (kv, ssm) = hymba_layer(cfg, params["hymba"], x, window=kind.window,
                                     q_offset=q_offset, cache=hc)
        x = _ffn_residual(cfg, kind, params, x + out)
        if kv is not None:
            new_cache["k"], new_cache["v"] = kv
        if ssm is not None:
            new_cache.update({"s": ssm["s"], "conv": ssm["conv"]})
        return x, new_cache
    if kind.block == "mlstm":
        st = None if cache is None else {"s": cache["s"], "conv": cache["conv"]}
        out, ns = mlstm_block(cfg, params["mlstm"], x, state=st)
        return x + out, (ns or {})
    if kind.block == "slstm":
        st = None if cache is None else {k: cache[k] for k in ("c", "n", "h")}
        out, ns = slstm_block(cfg, params["slstm"], x, state=st)
        return x + out, (ns or {})
    raise ValueError(kind.block)


def _slice_layer(stacked: jax.Array, i) -> jax.Array:
    """(count, ...) -> (...) at layer index i (traced)."""
    return jax.lax.dynamic_index_in_dim(stacked, i, axis=0, keepdims=False)


def _write_layer(stacked: jax.Array, value: jax.Array, i) -> jax.Array:
    return jax.lax.dynamic_update_slice(
        stacked, value[None].astype(stacked.dtype),
        (i,) + (0,) * value.ndim)


def _quant_kv(x: jax.Array):
    """(B, 1, K, D) -> int8 values + per (B, 1, K) scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _decode_layer(cfg: ModelConfig, kind: LayerKind, params: dict, x: jax.Array,
                  stacked: Dict[str, jax.Array], i, pos):
    """One decode layer against the stacked cache buffers (in-place column
    writes). Returns (x, new_stacked)."""
    ns = dict(stacked)
    if kind.block in ("attn", "xdec"):
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        k_new, v_new = project_kv_token(cfg, params["attn"], h, pos)
        int8 = "k_scale" in stacked
        if int8:
            k_new, ks = _quant_kv(k_new)
            v_new, vs = _quant_kv(v_new)
            ns["k_scale"] = jax.lax.dynamic_update_slice(
                stacked["k_scale"], ks[None], (i, 0, pos, 0))
            ns["v_scale"] = jax.lax.dynamic_update_slice(
                stacked["v_scale"], vs[None], (i, 0, pos, 0))
        # write only this token's column at (layer i, :, pos)
        ns["k"] = jax.lax.dynamic_update_slice(
            stacked["k"], k_new[None].astype(stacked["k"].dtype), (i, 0, pos, 0, 0))
        ns["v"] = jax.lax.dynamic_update_slice(
            stacked["v"], v_new[None].astype(stacked["v"].dtype), (i, 0, pos, 0, 0))
        if int8:
            # dequantize in-register at read time (int8 HBM traffic)
            kq = _slice_layer(ns["k"], i).astype(cfg.dtype)
            vq = _slice_layer(ns["v"], i).astype(cfg.dtype)
            ksc = _slice_layer(ns["k_scale"], i).astype(cfg.dtype)
            vsc = _slice_layer(ns["v_scale"], i).astype(cfg.dtype)
            lc = {"k": kq * ksc[..., None], "v": vq * vsc[..., None],
                  "pos": pos}
        else:
            lc = {"k": _slice_layer(ns["k"], i), "v": _slice_layer(ns["v"], i),
                  "pos": pos}
        a, _ = attention_block(cfg, params["attn"], h, causal=True,
                               window=kind.window, cache=lc, prewritten=True)
        x = x + a
        if kind.block == "xdec":
            hc = rms_norm(x, params["ln_cross"], cfg.norm_eps)
            cc = {"k": _slice_layer(stacked["ck"], i),
                  "v": _slice_layer(stacked["cv"], i), "pos": pos}
            ca, _ = attention_block(cfg, params["cross"], hc, causal=False,
                                    use_rope=False, cache=cc, cross_cached=True)
            x = x + ca
        return _ffn_residual(cfg, kind, params, x), ns
    if kind.block == "hymba":
        h = rms_norm(x, params["hymba"]["norm"], cfg.norm_eps)
        k_new, v_new = project_kv_token(cfg, params["hymba"]["attn"], h, pos)
        ns["k"] = jax.lax.dynamic_update_slice(
            stacked["k"], k_new[None].astype(stacked["k"].dtype), (i, 0, pos, 0, 0))
        ns["v"] = jax.lax.dynamic_update_slice(
            stacked["v"], v_new[None].astype(stacked["v"].dtype), (i, 0, pos, 0, 0))
        lc = {"k": _slice_layer(ns["k"], i), "v": _slice_layer(ns["v"], i),
              "pos": pos, "s": _slice_layer(stacked["s"], i),
              "conv": _slice_layer(stacked["conv"], i)}
        out, (_, ssm) = hymba_layer(cfg, params["hymba"], x, window=kind.window,
                                    cache=lc, prewritten=True)
        x = _ffn_residual(cfg, kind, params, x + out)
        ns["s"] = _write_layer(stacked["s"], ssm["s"], i)
        ns["conv"] = _write_layer(stacked["conv"], ssm["conv"], i)
        return x, ns
    if kind.block == "mlstm":
        st = {"s": _slice_layer(stacked["s"], i),
              "conv": _slice_layer(stacked["conv"], i)}
        out, nst = mlstm_block(cfg, params["mlstm"], x, state=st)
        ns["s"] = _write_layer(stacked["s"], nst["s"], i)
        ns["conv"] = _write_layer(stacked["conv"], nst["conv"], i)
        return x + out, ns
    if kind.block == "slstm":
        st = {k: _slice_layer(stacked[k], i) for k in ("c", "n", "h")}
        out, nst = slstm_block(cfg, params["slstm"], x, state=st)
        for k in ("c", "n", "h"):
            ns[k] = _write_layer(stacked[k], nst[k], i)
        return x + out, ns
    raise ValueError(kind.block)


def _empty_paged_for(cfg: ModelConfig, kind: LayerKind, n_slots: int,
                     n_pages: int, page_size: int, dtype) -> Dict[str, Any]:
    """Per-layer paged-serving buffers: attention KV lives in a shared page
    pool ``(n_pages, page_size, K, D)`` (block-table indirection picks a
    sequence's pages); recurrent state is per-slot ``(n_slots, ...)``."""
    out: Dict[str, Any] = {}
    if kind.block in ("attn", "hymba"):
        k, hd = cfg.n_kv_heads, cfg.hd
        int8 = cfg.kv_cache_dtype == "int8" and kind.block == "attn"
        cdt = jnp.int8 if int8 else dtype
        out["k"] = jnp.zeros((n_pages, page_size, k, hd), cdt)
        out["v"] = jnp.zeros((n_pages, page_size, k, hd), cdt)
        if int8:
            out["k_scale"] = jnp.zeros((n_pages, page_size, k), jnp.float32)
            out["v_scale"] = jnp.zeros((n_pages, page_size, k), jnp.float32)
    if kind.block == "xdec":
        raise NotImplementedError("paged decode does not cover enc-dec")
    if kind.block == "hymba":
        h, p, n = cfg.n_heads, cfg.hd, cfg.ssm_state
        out["s"] = jnp.zeros((n_slots, h, n, p), jnp.float32)
        out["conv"] = jnp.zeros((n_slots, cfg.ssm_conv - 1, h * p), dtype)
    if kind.block == "mlstm":
        d, d_inner, h, dk, dv = xlstm_dims(cfg)
        out["s"] = jnp.zeros((n_slots, h, dk, dv + 1), jnp.float32)
        out["conv"] = jnp.zeros((n_slots, cfg.ssm_conv - 1, d_inner), dtype)
    if kind.block == "slstm":
        h = cfg.n_heads
        dh = cfg.d_model // h
        for f in ("c", "n", "h"):
            out[f] = jnp.zeros((n_slots, h, dh), jnp.float32)
    return out


def _decode_layer_paged(cfg: ModelConfig, kind: LayerKind, params: dict,
                        x: jax.Array, stacked: Dict[str, jax.Array], i,
                        block_table, lens):
    """One decode layer over the paged state: write this token's K/V into
    its page slot at (block_table[b, lens[b]//PS], lens[b]%PS), then attend
    through the block-table indirection.  Recurrent blocks carry per-slot
    state exactly like the dense path.  Returns (x, new_stacked)."""
    if kind.block in ("mlstm", "slstm"):
        return _decode_layer(cfg, kind, params, x, stacked, i,
                             jnp.zeros((), jnp.int32))
    ns = dict(stacked)
    page_size = stacked["k"].shape[2]                # (L, n_pages, PS, K, D)
    pidx = jnp.take_along_axis(block_table, (lens // page_size)[:, None],
                               axis=1)[:, 0]         # (B,) physical page
    off = lens % page_size

    def write_token(h, attn_params):
        k_new, v_new = project_kv_token(cfg, attn_params, h, lens)
        int8 = "k_scale" in stacked
        if int8:
            k_new, ksc = _quant_kv(k_new)
            v_new, vsc = _quant_kv(v_new)
            ns["k_scale"] = stacked["k_scale"].at[i, pidx, off].set(ksc[:, 0])
            ns["v_scale"] = stacked["v_scale"].at[i, pidx, off].set(vsc[:, 0])
        ns["k"] = stacked["k"].at[i, pidx, off].set(
            k_new[:, 0].astype(stacked["k"].dtype))
        ns["v"] = stacked["v"].at[i, pidx, off].set(
            v_new[:, 0].astype(stacked["v"].dtype))
        if int8:
            # int8 pools: dequantize a gathered dense view (the fused paged
            # kernel path is bf16-only)
            from repro.kernels.decode_attention.ref import gather_pages
            kd = gather_pages(_slice_layer(ns["k"], i), block_table).astype(cfg.dtype)
            vd = gather_pages(_slice_layer(ns["v"], i), block_table).astype(cfg.dtype)
            b, p = block_table.shape
            ksc = jnp.take(_slice_layer(ns["k_scale"], i), block_table,
                           axis=0).reshape(b, p * page_size, -1)
            vsc = jnp.take(_slice_layer(ns["v_scale"], i), block_table,
                           axis=0).reshape(b, p * page_size, -1)
            return {"k": kd * ksc[..., None].astype(cfg.dtype),
                    "v": vd * vsc[..., None].astype(cfg.dtype), "pos": lens}
        return {"k_pages": _slice_layer(ns["k"], i),
                "v_pages": _slice_layer(ns["v"], i),
                "block_table": block_table, "pos": lens}

    if kind.block == "attn":
        with jax.named_scope("attention"):
            h = rms_norm(x, params["ln1"], cfg.norm_eps)
            lc = write_token(h, params["attn"])
            a, _ = attention_block(cfg, params["attn"], h, causal=True,
                                   window=kind.window, cache=lc,
                                   prewritten=True)
        return _ffn_residual(cfg, kind, params, x + a), ns
    if kind.block == "hymba":
        h = rms_norm(x, params["hymba"]["norm"], cfg.norm_eps)
        lc = write_token(h, params["hymba"]["attn"])
        lc.update({"s": _slice_layer(stacked["s"], i),
                   "conv": _slice_layer(stacked["conv"], i)})
        out, (_, ssm) = hymba_layer(cfg, params["hymba"], x, window=kind.window,
                                    cache=lc, prewritten=True)
        x = _ffn_residual(cfg, kind, params, x + out)
        ns["s"] = _write_layer(stacked["s"], ssm["s"], i)
        ns["conv"] = _write_layer(stacked["conv"], ssm["conv"], i)
        return x, ns
    raise ValueError(kind.block)


def _verify_layer_paged(cfg: ModelConfig, kind: LayerKind, params: dict,
                        x: jax.Array, stacked: Dict[str, jax.Array], i,
                        block_table, lens):
    """Speculative-verify twin of ``_decode_layer_paged``: ``x`` carries S
    tokens per sequence sitting at positions ``lens[b] .. lens[b]+S-1``.
    All S K/V columns are written into the page pool, then ONE multi-position
    prewritten attention pass scores every position (query s masked to
    positions <= lens[b]+s).  Per-position numerics are the S-batched form of
    the decode-step ops, so slice s is bit-identical to the sequential decode
    step at the same position.  Recurrent state (mlstm/slstm/hymba) advances
    token-by-token and cannot be batch-verified — those families are fenced
    at trace time."""
    if kind.block != "attn":
        raise NotImplementedError(
            "speculative verify requires pure-attention layers; "
            f"got {kind.block!r} (recurrent state advances token-by-token)")
    ns = dict(stacked)
    page_size = stacked["k"].shape[2]                # (L, n_pages, PS, K, D)
    s_q = x.shape[1]
    pos2 = lens[:, None] + jnp.arange(s_q, dtype=jnp.int32)[None, :]  # (B,S)
    pidx = jnp.take_along_axis(block_table, pos2 // page_size, axis=1)
    off = pos2 % page_size

    def write_tokens(h, attn_params):
        k_new, v_new = project_kv_token(cfg, attn_params, h, lens)
        int8 = "k_scale" in stacked
        if int8:
            k_new, ksc = _quant_kv(k_new)
            v_new, vsc = _quant_kv(v_new)
            ns["k_scale"] = stacked["k_scale"].at[i, pidx, off].set(ksc)
            ns["v_scale"] = stacked["v_scale"].at[i, pidx, off].set(vsc)
        ns["k"] = stacked["k"].at[i, pidx, off].set(
            k_new.astype(stacked["k"].dtype))
        ns["v"] = stacked["v"].at[i, pidx, off].set(
            v_new.astype(stacked["v"].dtype))
        if int8:
            from repro.kernels.decode_attention.ref import gather_pages
            kd = gather_pages(_slice_layer(ns["k"], i), block_table).astype(cfg.dtype)
            vd = gather_pages(_slice_layer(ns["v"], i), block_table).astype(cfg.dtype)
            b, p = block_table.shape
            ksc = jnp.take(_slice_layer(ns["k_scale"], i), block_table,
                           axis=0).reshape(b, p * page_size, -1)
            vsc = jnp.take(_slice_layer(ns["v_scale"], i), block_table,
                           axis=0).reshape(b, p * page_size, -1)
            return {"k": kd * ksc[..., None].astype(cfg.dtype),
                    "v": vd * vsc[..., None].astype(cfg.dtype), "pos": lens}
        return {"k_pages": _slice_layer(ns["k"], i),
                "v_pages": _slice_layer(ns["v"], i),
                "block_table": block_table, "pos": lens}

    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    lc = write_tokens(h, params["attn"])
    a, _ = attention_block(cfg, params["attn"], h, causal=True,
                           window=kind.window, cache=lc, prewritten=True)
    return _ffn_residual(cfg, kind, params, x + a), ns


class DecoderLM:
    """Dense / MoE / hybrid / xLSTM decoder language model."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan = layer_plan(cfg)

    # -- declarations --------------------------------------------------
    def decls(self) -> dict:
        cfg = self.cfg
        segs = []
        for count, pattern in self.plan:
            segs.append([_stack(_layer_decls(cfg, k), count) for k in pattern])
        d = {
            "embed": embed_decls(cfg.padded_vocab, cfg.d_model),
            "final_norm": norm_decl(cfg.d_model),
            "segs": segs,
        }
        if not cfg.tie_embeddings:
            d["out_embed"] = embed_decls(cfg.padded_vocab, cfg.d_model)
        return d

    def init(self, key: jax.Array):
        return init_params(self.decls(), key)

    def _out_table(self, params):
        return params.get("out_embed", params["embed"])

    # -- embedding -----------------------------------------------------
    def _embed_input(self, params, tokens: Optional[jax.Array],
                     embeds: Optional[jax.Array]):
        cfg = self.cfg
        parts = []
        if embeds is not None:
            parts.append(embeds.astype(cfg.dtype))
        if tokens is not None:
            parts.append(embed_lookup(params["embed"], tokens))
        x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        return logical_shard(x, "batch", "seq", "embed")

    # -- full-sequence forward ------------------------------------------
    def hidden(self, params, tokens=None, embeds=None, q_offset: int = 0):
        cfg = self.cfg
        x = self._embed_input(params, tokens, embeds)
        for si, (count, pattern) in enumerate(self.plan):
            seg_params = params["segs"][si]

            def body(x, lp, _pattern=pattern):
                for j, kind in enumerate(_pattern):
                    x, _ = _apply_layer(cfg, kind, lp[j], x, q_offset=q_offset)
                return x, None

            if cfg.remat != "none":
                body = jax.checkpoint(body, prevent_cse=False)
            x, _ = jax.lax.scan(body, x, seg_params)
        return rms_norm(x, params["final_norm"], cfg.norm_eps)

    # -- training loss ----------------------------------------------------
    def loss(self, params, batch: dict) -> jax.Array:
        cfg = self.cfg
        tokens = batch["tokens"]
        embeds = batch.get("embeds")
        h = self.hidden(params, tokens, embeds)
        b, s, _ = h.shape
        flen = 0 if embeds is None else embeds.shape[1]
        padded = tokens if flen == 0 else jnp.concatenate(
            [jnp.zeros((b, flen), tokens.dtype), tokens], axis=1)
        labels = jnp.roll(padded, -1, axis=1)
        posn = jnp.arange(s)
        mask = (posn >= max(flen - 1, 0)) & (posn < s - 1)
        mask = jnp.broadcast_to(mask[None, :], (b, s))
        if "mask" in batch and batch["mask"] is not None:
            mask = mask & (batch["mask"] > 0)
        return chunked_softmax_xent(self._out_table(params), h, labels, mask,
                                    cfg.vocab_size, cfg.logit_chunk)

    def logits(self, params, tokens=None, embeds=None):
        h = self.hidden(params, tokens, embeds)
        table = self._out_table(params)
        out = (h @ table.T).astype(jnp.float32)
        return logical_shard(out, "batch", "seq", "vocab_act")

    # -- caches -------------------------------------------------------------
    def empty_cache(self, batch: int, t_max: int) -> dict:
        cfg = self.cfg
        segs = []
        for count, pattern in self.plan:
            seg = []
            for kind in pattern:
                one = _empty_cache_for(cfg, kind, batch, t_max, cfg.dtype)
                one = {k: v for k, v in one.items() if v is not None}
                seg.append(jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one))
            segs.append(seg)
        return {"pos": jnp.zeros((), jnp.int32), "segs": segs}

    def empty_paged_state(self, n_slots: int, n_pages: int,
                          page_size: int) -> dict:
        """Fixed-shape serving state: KV page pools shared by ``n_slots``
        sequence slots (block-table indirection) + per-slot recurrent state.
        Unlike ``empty_cache`` there is no global ``pos`` — per-sequence
        lengths are an input of ``decode_step_paged``."""
        cfg = self.cfg
        segs = []
        for count, pattern in self.plan:
            seg = []
            for kind in pattern:
                one = _empty_paged_for(cfg, kind, n_slots, n_pages,
                                       page_size, cfg.dtype)
                seg.append(jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one))
            segs.append(seg)
        return {"segs": segs}

    # -- prefill: build cache over a prompt ---------------------------------
    def prefill(self, params, tokens=None, embeds=None):
        cfg = self.cfg
        x = self._embed_input(params, tokens, embeds)
        s = x.shape[1]
        cache_segs: List[list] = []
        for si, (count, pattern) in enumerate(self.plan):
            seg_params = params["segs"][si]

            def body(x, lp, _pattern=pattern):
                caches = []
                for j, kind in enumerate(_pattern):
                    x, nc = _apply_layer(cfg, kind, lp[j], x, q_offset=0)
                    caches.append(nc)
                return x, caches

            x, seg_cache = jax.lax.scan(body, x, seg_params)
            cache_segs.append(seg_cache)
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = h[:, -1]
        logits = (last @ self._out_table(params).T).astype(jnp.float32)
        cache = {"pos": jnp.asarray(s, jnp.int32), "segs": cache_segs}
        return cache, logits

    # -- single-token decode --------------------------------------------------
    #
    # The stacked cache rides the scan CARRY and each layer writes only its
    # one-token column via dynamic_update_slice at (layer, :, pos) — the naive
    # xs/ys formulation rewrites the full per-layer cache every step (measured
    # ~65x decode HBM traffic; see EXPERIMENTS.md §Perf).
    def decode_step(self, params, cache: dict, token: jax.Array):
        """token: (B, 1) int32. Returns (new_cache, logits (B, V))."""
        cfg = self.cfg
        pos = cache["pos"]
        x = self._embed_input(params, token, None)
        new_segs: List[list] = []
        for si, (count, pattern) in enumerate(self.plan):
            seg_params = params["segs"][si]
            seg_cache = tuple(cache["segs"][si])

            def body(carry, lp, _pattern=pattern):
                x, sc, i = carry
                sc = list(sc)
                for j, kind in enumerate(_pattern):
                    x, sc[j] = _decode_layer(cfg, kind, lp[j], x, sc[j], i, pos)
                return (x, tuple(sc), i + 1), None

            init = (x, seg_cache, jnp.zeros((), jnp.int32))
            (x, seg_cache, _), _ = jax.lax.scan(body, init, seg_params)
            new_segs.append(list(seg_cache))
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,vd->bv", h[:, -1], self._out_table(params),
                            preferred_element_type=jnp.float32)
        return {"pos": pos + 1, "segs": new_segs}, logits

    # -- paged single-token decode -------------------------------------------
    #
    # The serving-plane twin of decode_step: the KV cache is a page pool with
    # a (B, P) block table, every sequence sits at its own position
    # (lens: (B,)), and shapes depend only on (n_slots, n_pages, page_size) —
    # admissions and completions never change them, so one compilation
    # serves the endpoint's whole lifetime.
    def decode_step_paged(self, params, state: dict, token: jax.Array,
                          block_table: jax.Array, lens: jax.Array):
        """token: (B,1) int32; block_table (B,P) int32 physical page ids;
        lens (B,) int32 tokens already in cache. Returns (new_state, logits).
        The token's K/V is written at position lens[b] (page
        block_table[b, lens[b]//PS]); the caller advances ``lens``."""
        cfg = self.cfg
        block_table = jnp.asarray(block_table, jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)
        x = self._embed_input(params, token, None)
        new_segs: List[list] = []
        for si, (count, pattern) in enumerate(self.plan):
            seg_params = params["segs"][si]
            seg_state = tuple(state["segs"][si])

            def body(carry, lp, _pattern=pattern):
                x, sc, i = carry
                sc = list(sc)
                for j, kind in enumerate(_pattern):
                    x, sc[j] = _decode_layer_paged(cfg, kind, lp[j], x, sc[j],
                                                   i, block_table, lens)
                return (x, tuple(sc), i + 1), None

            init = (x, seg_state, jnp.zeros((), jnp.int32))
            (x, seg_state, _), _ = jax.lax.scan(body, init, seg_params)
            new_segs.append(list(seg_state))
        with jax.named_scope("head"):
            h = rms_norm(x, params["final_norm"], cfg.norm_eps)
            logits = jnp.einsum("bd,vd->bv", h[:, -1],
                                self._out_table(params),
                                preferred_element_type=jnp.float32)
        return {"segs": new_segs}, logits

    # -- paged multi-position verify (speculative cascade) --------------------
    #
    # The strong endpoint scores all S draft positions in ONE pass: layer
    # numerics are the S-batched form of the decode-step ops (same operand
    # dtypes, same fp32 accumulation), so logits[:, s] is bit-identical to
    # the sequential decode_step_paged logits at position lens + s — the
    # property the acceptance loop's "speculative greedy == strong-only
    # greedy" guarantee rests on.
    def verify_step_paged(self, params, state: dict, tokens: jax.Array,
                          block_table: jax.Array, lens: jax.Array):
        """tokens: (B,S) int32 — token s is the input at position lens[b]+s
        (its K/V is written there); block_table (B,P); lens (B,) int32.
        Returns (new_state, logits (B,S,V)): logits[:, s] scores the token
        FOLLOWING position lens+s.  Attention-family layers only — recurrent
        blocks raise NotImplementedError at trace time."""
        cfg = self.cfg
        block_table = jnp.asarray(block_table, jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)
        x = self._embed_input(params, tokens, None)
        new_segs: List[list] = []
        for si, (count, pattern) in enumerate(self.plan):
            seg_params = params["segs"][si]
            seg_state = tuple(state["segs"][si])

            def body(carry, lp, _pattern=pattern):
                x, sc, i = carry
                sc = list(sc)
                for j, kind in enumerate(_pattern):
                    x, sc[j] = _verify_layer_paged(cfg, kind, lp[j], x, sc[j],
                                                   i, block_table, lens)
                return (x, tuple(sc), i + 1), None

            init = (x, seg_state, jnp.zeros((), jnp.int32))
            (x, seg_state, _), _ = jax.lax.scan(body, init, seg_params)
            new_segs.append(list(seg_state))
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,vd->bsv", h, self._out_table(params),
                            preferred_element_type=jnp.float32)
        return {"segs": new_segs}, logits
