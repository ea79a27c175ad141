"""Building blocks of the families' plain references
(``bench/families/<family>.py:logits``).

A reference runs in float32 at ``Precision.HIGHEST`` over several requests
packed into one row: each position attends to earlier positions of its own
request, and positions restart at 0 for every request.  One program shape
(``T`` positions) serves every run.  It imports nothing of the program.

Its control, ``lowp=True``, rounds every linear layer's operands to float8
e4m3 (per-tensor scale for weights, per-row for activations) with float32
accumulation, the precision step below the bf16 the configurations serve
in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def q8(x, axis):
    """Round to float8 e4m3 under an absmax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(x, w, spec, lowp):
    """``einsum(spec, x, w)`` in float32; the control rounds both operands
    (activations per row over their contracted dim, weights per tensor)."""
    if lowp:
        x = q8(x, -1)
        w = q8(w, tuple(range(w.ndim)))
    return jnp.einsum(spec, x, w)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, half)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
