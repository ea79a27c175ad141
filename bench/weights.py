"""Weights of a configuration's full-width member, made from the run seed.

One jitted call draws every leaf on the device in bf16, the type it is
served in.  Both the served endpoint and the plain reference take their
weights from here, each calling it with the seed; neither reads the
other's copy.

The scales give unit-variance pre-activations (fan-in scaling on every
projection), as a trained model roughly has.  Random weights at the
program's own init scales saturate attention (q·k logits of order 100), and
such a model's greedy tokens flip on rounding alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sizes(cfg: dict) -> dict:
    """The shape numbers of a configuration file, by short name."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return dict(L=int(cfg["num_hidden_layers"]), d=d, H=h,
                K=int(cfg["num_key_value_heads"]),
                D=int(cfg.get("head_dim") or d // h),
                F=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
                Vp=-(-int(cfg["vocab_size"]) // 256) * 256)


def shapes(s: dict) -> dict:
    L, d, H, K, D, F, Vp = (s[k] for k in ("L", "d", "H", "K", "D", "F",
                                           "Vp"))
    return {"embed": (Vp, d), "out_embed": (Vp, d), "final_norm": (d,),
            "ln1": (L, d), "ln2": (L, d),
            "wq": (L, d, H, D), "wk": (L, d, K, D), "wv": (L, d, K, D),
            "wo": (L, H, D, d),
            "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d)}


def _std(name: str, s: dict) -> float:
    if name == "embed":
        return 1.0
    if name == "wo":
        return float(1.0 / np.sqrt(s["H"] * s["D"]))
    if name == "w_down":
        return float(1.0 / np.sqrt(s["F"]))
    return float(1.0 / np.sqrt(s["d"]))     # wq wk wv w_gate w_up out_embed


def seed_key(seed: int, purpose: int) -> jax.Array:
    """A PRNG key from any whole-number seed (up to 64 bits)."""
    word = np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF,
         purpose]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


@functools.partial(jax.jit, static_argnames=("frozen",))
def _draw(key, frozen):
    s = dict(frozen)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(s).items())):
        if name in ("final_norm", "ln1", "ln2"):
            out[name] = jnp.ones(shape, jnp.bfloat16)
            continue
        k = jax.random.fold_in(key, i)
        out[name] = (_std(name, s) * jax.random.normal(k, shape, jnp.float32)
                     ).astype(jnp.bfloat16)
    return out


def make(cfg: dict, seed: int) -> dict:
    """bf16 weights of the configuration's full-width member for ``seed``."""
    return _draw(seed_key(seed, 7), tuple(sorted(sizes(cfg).items())))
