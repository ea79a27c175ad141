"""Weights of a configuration's full-width member, made from the run seed.

One jitted call draws every leaf on the device in bf16, the type it is
served in.  Both the served endpoint and the plain reference take their
weights from here, each calling it with the seed; neither reads the
other's copy.  The leaves, their shapes and scales are the member's
family's (``bench.families``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import families


def seed_key(seed: int, purpose: int) -> jax.Array:
    """A PRNG key from any whole-number seed (up to 64 bits)."""
    word = np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF,
         purpose]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


@functools.partial(jax.jit, static_argnames=("leaves",))
def _draw(key, leaves):
    out = {}
    for i, (name, shape, std) in enumerate(leaves):
        if std is None:
            out[name] = jnp.ones(shape, jnp.bfloat16)
            continue
        k = jax.random.fold_in(key, i)
        out[name] = (std * jax.random.normal(k, shape, jnp.float32)
                     ).astype(jnp.bfloat16)
    return out


def make(cfg: dict, seed: int) -> dict:
    """bf16 weights of the configuration's full-width member for ``seed``:
    its family's leaves, in sorted order, each from the seed's key folded
    with its index."""
    fam = families.load(cfg)
    s = fam.sizes(cfg)
    leaves = tuple((name, tuple(shape), fam.scale(name, s))
                   for name, shape in sorted(fam.shapes(s).items()))
    return _draw(seed_key(seed, 7), leaves)
