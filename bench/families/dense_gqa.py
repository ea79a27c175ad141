"""The dense GQA decoder (Llama-style: RMSNorm, rotary embedding on
half-split dims, grouped-query causal attention, SwiGLU MLP, untied output
head), as the published configurations of h2o-danube3 and InternLM2
describe it.  The interface is in ``bench/families/__init__.py``.
"""
from __future__ import annotations

import functools
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import linear, rms, rope

# configuration-file key -> ModelConfig field of the full-width member
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "vocab_size": "vocab_size", "rope_theta": "rope_theta",
          "sliding_window": "sliding_window", "rms_norm_eps": "norm_eps",
          "tie_word_embeddings": "tie_embeddings"}


def main_sizes(cfg: dict, mcfg) -> dict:
    """The numbers the weights and the reference take, read back from the
    ModelConfig actually served (smoke or full)."""
    return dict(cfg, num_hidden_layers=mcfg.n_layers, hidden_size=mcfg.d_model,
                intermediate_size=mcfg.d_ff,
                num_attention_heads=mcfg.n_heads,
                num_key_value_heads=mcfg.n_kv_heads, head_dim=mcfg.hd,
                vocab_size=mcfg.vocab_size, rope_theta=mcfg.rope_theta,
                sliding_window=mcfg.sliding_window, rms_norm_eps=mcfg.norm_eps)


# --- weights ---------------------------------------------------------------

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


def scale(name: str, s: dict):
    """Fan-in scaling on every projection: unit-variance pre-activations,
    as a trained model roughly has.  Random weights at the program's own
    init scales saturate attention (q·k logits of order 100), and such a
    model's greedy tokens flip on rounding alone."""
    if name in ("final_norm", "ln1", "ln2"):
        return None
    if name == "embed":
        return 1.0
    if name == "wo":
        return float(1.0 / np.sqrt(s["H"] * s["D"]))
    if name == "w_down":
        return float(1.0 / np.sqrt(s["F"]))
    return float(1.0 / np.sqrt(s["d"]))     # wq wk wv w_gate w_up out_embed


def program_tree(w: dict, mcfg) -> dict:
    """The drawn leaves in the program's parameter tree: one scanned
    segment of identical attention layers, whatever ``mcfg``."""
    layer = {"attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
             "ln1": w["ln1"], "ln2": w["ln2"]}
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "out_embed": w["out_embed"], "segs": [[layer]]}


# --- plain reference -------------------------------------------------------
#
# float32 at Precision.HIGHEST over several requests packed into one row:
# each position attends to earlier positions of its own request, and
# positions restart at 0 for every request.  ``lowp=True`` is the control:
# every linear layer's operands rounded to float8 e4m3 (per-tensor scale for
# weights, per-row for activations) with float32 accumulation, the precision
# step below the bf16 the configuration serves in.

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
        h = rms(x, lw["ln1"], eps)
        q = rope(linear(h, lw["wq"], "td,dhk->thk", lowp), pos, theta)
        k = rope(linear(h, lw["wk"], "td,dhk->thk", lowp), pos, theta)
        v = linear(h, lw["wv"], "td,dhk->thk", lowp)
        d = q.shape[-1]
        qg = q.reshape(t, K, g, d)
        s = jnp.einsum("tkgd,ukd->kgtu", qg, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgtu,ukd->tkgd", p, v).reshape(t, H, d)
        x = x + linear(o, lw["wo"], "thk,hkd->td", lowp)
        h = rms(x, lw["ln2"], eps)
        a = jax.nn.silu(linear(h, lw["w_gate"], "td,df->tf", lowp))
        a = a * linear(h, lw["w_up"], "td,df->tf", lowp)
        return x + linear(a, lw["w_down"], "tf,fd->td", lowp), None

    stacked = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                                  "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(layer, x, stacked)
    h = rms(x, w["final_norm"].astype(jnp.float32), eps)
    head = w["out_embed"][: c["V"]].astype(jnp.float32)
    return linear(h, head, "td,vd->tv", lowp)


def config_consts(cfg: dict) -> tuple:
    s = sizes(cfg)
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


# --- work counts -----------------------------------------------------------
#
# Operations and bytes the algorithm needs, from shapes alone.  Counts are of
# the useful work: real prompt tokens, decoded tokens of live slots, keys and
# values at the live lengths.  Padding, gathers of unused cache positions and
# recomputation do not count, so a share computed from them reads the same
# whatever implements the layer, and cannot pass 100%.

def matmul_params(s: dict) -> int:
    """Parameters multiplied per token: every layer's projections and MLP,
    and the output head (over the real vocabulary)."""
    d, H, K, D, F = s["d"], s["H"], s["K"], s["D"], s["F"]
    per_layer = d * H * D + 2 * d * K * D + H * D * d + 3 * d * F
    return s["L"] * per_layer + s["V"] * d


def weight_bytes(s: dict, dtype_bytes: int = 2) -> int:
    """Bytes of the weights a decode step must read once: all layers, the
    final norm and the head (the embedding is read a row per token)."""
    d = s["d"]
    layer_norms = 2 * d
    return dtype_bytes * (matmul_params(s) + s["L"] * layer_norms + d)


def kv_bytes_per_token(s: dict, dtype_bytes: int = 2) -> int:
    return 2 * s["L"] * s["K"] * s["D"] * dtype_bytes


def attn_flops(s: dict, kv_len_sum: int) -> int:
    """QK^T and PV over ``kv_len_sum`` (query, key) pairs, all layers."""
    return 4 * s["L"] * s["H"] * s["D"] * kv_len_sum


def step_floor_bytes(ctx) -> int:
    """Every step of the member reads all its weights at least once."""
    return weight_bytes(ctx.s)


def decode_chunk(ctx, lens: Iterable[int], remaining: Iterable[int],
                 steps: int) -> Tuple[int, int]:
    """(FLOPs, least bytes) of one decode chunk of ``steps`` steps over the
    live slots (``lens`` tokens cached, ``remaining`` still to emit).  Each
    step reads the weights once and each live slot's keys and values up to
    its length, and writes one token's keys and values per live slot."""
    s = ctx.s
    flops = bytes_ = 0
    mp = matmul_params(s)
    wb = weight_bytes(s)
    kvt = kv_bytes_per_token(s)
    lens = [int(n) for n in lens]
    remaining = [int(r) for r in remaining]
    for i in range(steps):
        live = [n + i + 1 for n, r in zip(lens, remaining) if r > i]
        if not live:
            continue
        flops += 2 * mp * len(live) + attn_flops(s, sum(live))
        bytes_ += wb + s["d"] * 2 * len(live) + kvt * (sum(live))
    return flops, bytes_


def prefill(ctx, plen: int) -> int:
    """FLOPs of a prefill of ``plen`` real tokens: every projection and MLP
    per token, causal attention, and the head for the last position."""
    s = ctx.s
    d = s["d"]
    mp_body = matmul_params(s) - s["V"] * d
    return (2 * mp_body * plen + attn_flops(s, plen * (plen + 1) // 2)
            + 2 * s["V"] * d)
