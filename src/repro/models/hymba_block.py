"""Hymba layer: parallel attention heads + SSD (Mamba-2 style) heads on the
same input, per arXiv:2411.13676. Branch outputs are normalized and averaged
with learnable per-branch scales before the output projection.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common import ParamDecl, logical_shard
from repro.configs.base import ModelConfig
from .attention import attn_decls, attention_block
from .layers import causal_conv1d, rms_norm
from .ssm import chunked_gla, gla_decode_step


def ssd_decls(cfg: ModelConfig) -> dict:
    d, h, p, n = cfg.d_model, cfg.n_heads, cfg.hd, cfg.ssm_state
    d_inner = h * p
    return {
        "w_x": ParamDecl((d, h, p), ("p_embed", "p_none", "p_none"), init="scaled"),
        "w_z": ParamDecl((d, h, p), ("p_embed", "p_none", "p_none"), init="scaled"),
        "w_b": ParamDecl((d, h, n), ("p_embed", "p_none", "p_none"), init="scaled"),
        "w_c": ParamDecl((d, h, n), ("p_embed", "p_none", "p_none"), init="scaled"),
        "w_dt": ParamDecl((d, h), ("p_embed", "p_none"), init="scaled",
                          dtype=jnp.float32),
        "dt_bias": ParamDecl((h,), ("p_none",), init="zeros", dtype=jnp.float32),
        "a_log": ParamDecl((h,), ("p_none",), init="zeros", dtype=jnp.float32),
        "d_skip": ParamDecl((h,), ("p_none",), init="ones", dtype=jnp.float32),
        "conv_w": ParamDecl((cfg.ssm_conv, d_inner), ("p_none", "p_none"),
                            init="scaled"),
    }


def ssd_branch(cfg: ModelConfig, params: dict, x: jax.Array, *,
               state: Optional[dict] = None):
    """SSD selective-state branch. x: (B,S,d) (normed). Returns (out, state)."""
    b, s, d = x.shape
    h, p, n = cfg.n_heads, cfg.hd, cfg.ssm_state
    xh = jnp.einsum("bsd,dhp->bshp", x, params["w_x"])
    conv_state = state["conv"] if state is not None else None
    xf = xh.reshape(b, s, h * p)
    xf, conv_tail = causal_conv1d(xf, params["conv_w"], conv_state)
    xh = jax.nn.silu(xf).reshape(b, s, h, p)

    bmat = jnp.einsum("bsd,dhn->bshn", x, params["w_b"])
    cmat = jnp.einsum("bsd,dhn->bshn", x, params["w_c"])
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32), params["w_dt"])
        + params["dt_bias"]
    )
    log_a = -dt * jnp.exp(params["a_log"])            # (B,S,H) decay in log space
    v = (xh.astype(jnp.float32) * dt[..., None]).astype(x.dtype)

    if state is None:
        y, final = chunked_gla(cmat, bmat, v, log_a, chunk=min(128, s))
        new_state = {"s": final, "conv": conv_tail}
    else:
        y, s_new = gla_decode_step(cmat[:, 0], bmat[:, 0], v[:, 0], log_a[:, 0],
                                   state["s"])
        y = y[:, None]
        new_state = {"s": s_new, "conv": conv_tail}

    y = y + xh * params["d_skip"].astype(x.dtype).reshape(1, 1, h, 1)
    z = jnp.einsum("bsd,dhp->bshp", x, params["w_z"])
    y = (y * jax.nn.silu(z)).reshape(b, y.shape[1], h * p)
    return y, new_state


def hymba_decls(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_inner = cfg.n_heads * cfg.hd
    return {
        "norm": ParamDecl((d,), ("p_none",), init="ones"),
        "attn": attn_decls(cfg),
        "ssd": ssd_decls(cfg),
        "attn_norm": ParamDecl((d_inner,), ("p_none",), init="ones"),
        "ssd_norm": ParamDecl((d_inner,), ("p_none",), init="ones"),
        "beta": ParamDecl((2,), ("p_none",), init="ones", dtype=jnp.float32),
    }


def hymba_layer(cfg: ModelConfig, params: dict, x: jax.Array, *,
                window: int = 0, q_offset=0, cache: Optional[dict] = None,
                prewritten: bool = False):
    """Parallel attn ∥ SSD. cache (decode): {'k','v','pos','s','conv'}.

    Returns (out, (new_kv, new_ssm_state))."""
    xn = rms_norm(x, params["norm"], cfg.norm_eps)
    attn_cache = None
    ssm_state = None
    if cache is not None:
        attn_cache = {k: cache[k] for k in
                      ("k", "v", "k_pages", "v_pages", "layer", "block_table",
                       "pos")
                      if k in cache}
        ssm_state = {"s": cache["s"], "conv": cache["conv"]}

    # attention branch produces (B,S,d) via its own wo; to mirror the paper we
    # average *pre-projection* head outputs — here we keep per-branch outputs
    # in model space and average, which is equivalent up to a linear map.
    attn_out, new_kv = attention_block(
        cfg, params["attn"], xn, causal=True, window=window,
        q_offset=q_offset, cache=attn_cache, prewritten=prewritten,
    )
    ssd_out, new_ssm = ssd_branch(cfg, params["ssd"], xn, state=ssm_state)
    # ssd_out is (B,S,H*P) = (B,S,d_inner); fold back with attn's wo pathway:
    ssd_out = jnp.einsum("bshk,hkd->bsd",
                         ssd_out.reshape(*ssd_out.shape[:2], cfg.n_heads, cfg.hd),
                         params["attn"]["wo"])
    beta = params["beta"]
    a = rms_norm(attn_out, params["attn_norm"], cfg.norm_eps)
    m = rms_norm(ssd_out, params["ssd_norm"], cfg.norm_eps)
    out = 0.5 * (beta[0] * a + beta[1] * m).astype(x.dtype)
    return logical_shard(out, "batch", "seq", "embed"), (new_kv, new_ssm)
