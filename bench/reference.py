"""Plain reference of the full-width member: a dense GQA decoder
(Llama-style: RMSNorm, rotary embedding on half-split dims, grouped-query
causal attention, SwiGLU MLP, untied output head), as the published
configurations of h2o-danube3 and InternLM2 describe it.

It runs in float32 at ``Precision.HIGHEST`` over several requests packed
into one row: each position attends to earlier positions of its own
request, and positions restart at 0 for every request.  One program shape
(``T`` positions) serves every run.  It imports nothing of the program.

``lowp=True`` is the control: every linear layer's operands are rounded to
float8 e4m3 (per-tensor scale for weights, per-row for activations) with
float32 accumulation, the precision step below the bf16 the configuration
serves in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W

E4M3_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 under an absmax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, spec, lowp):
    """``einsum(spec, x, w)`` in float32; the control rounds both operands
    (activations per row over their contracted dim, weights per tensor)."""
    if lowp:
        x = _q8(x, -1)
        w = _q8(w, tuple(range(w.ndim)))
    return jnp.einsum(spec, x, w)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, half)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("frozen", "lowp"))
def _forward(w, tokens, seg, pos, *, frozen, lowp):
    c = dict(frozen)
    eps, theta, window = c["eps"], c["theta"], c["window"]
    H, K = c["H"], c["K"]
    g = H // K
    t = tokens.shape[0]
    x = w["embed"][tokens].astype(jnp.float32)
    same = seg[:, None] == seg[None, :]
    causal = pos[None, :] <= pos[:, None]
    mask = same & causal
    if window > 0:
        mask = mask & (pos[None, :] > pos[:, None] - window)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
        h = _rms(x, lw["ln1"], eps)
        q = _rope(_linear(h, lw["wq"], "td,dhk->thk", lowp), pos, theta)
        k = _rope(_linear(h, lw["wk"], "td,dhk->thk", lowp), pos, theta)
        v = _linear(h, lw["wv"], "td,dhk->thk", lowp)
        d = q.shape[-1]
        qg = q.reshape(t, K, g, d)
        s = jnp.einsum("tkgd,ukd->kgtu", qg, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgtu,ukd->tkgd", p, v).reshape(t, H, d)
        x = x + _linear(o, lw["wo"], "thk,hkd->td", lowp)
        h = _rms(x, lw["ln2"], eps)
        a = jax.nn.silu(_linear(h, lw["w_gate"], "td,df->tf", lowp))
        a = a * _linear(h, lw["w_up"], "td,df->tf", lowp)
        return x + _linear(a, lw["w_down"], "tf,fd->td", lowp), None

    stacked = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                  "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(layer, x, stacked)
    h = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    head = w["out_embed"][: c["V"]].astype(jnp.float32)
    return _linear(h, head, "td,vd->tv", lowp)


def config_consts(cfg: dict) -> tuple:
    s = W.sizes(cfg)
    return tuple(sorted(dict(
        H=s["H"], K=s["K"], V=s["V"], eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]),
        window=int(cfg.get("sliding_window") or 0)).items()))


def logits(cfg: dict, w: dict, tokens, seg, pos, *, lowp: bool = False):
    """(T, V) float32 logits of the packed row; ``w`` from
    :func:`bench.weights.make`."""
    with jax.default_matmul_precision("highest"):
        return _forward(w, jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(seg, jnp.int32),
                        jnp.asarray(pos, jnp.int32),
                        frozen=config_consts(cfg), lowp=lowp)
