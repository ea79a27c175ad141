"""Operations and bytes the algorithm needs, from shapes alone.

``s`` is ``bench.weights.sizes`` of the full-width member.  Counts are of
the useful work: real prompt tokens, decoded tokens of live slots, keys and
values at the live lengths.  Padding, gathers of unused cache positions and
recomputation do not count, so a share computed from them reads the same
whatever implements the layer, and cannot pass 100%.
"""
from __future__ import annotations

from typing import Iterable, Tuple


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


def decode_chunk(s: dict, lens: Iterable[int], remaining: Iterable[int],
                 steps: int) -> Tuple[int, int]:
    """(FLOPs, least bytes) of one decode chunk of ``steps`` steps over the
    live slots (``lens`` tokens cached, ``remaining`` still to emit).  Each
    step reads the weights once and each live slot's keys and values up to
    its length, and writes one token's keys and values per live slot."""
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


def prefill(s: dict, plen: int) -> int:
    """FLOPs of a prefill of ``plen`` real tokens: every projection and MLP
    per token, causal attention, and the head for the last position."""
    d = s["d"]
    mp_body = matmul_params(s) - s["V"] * d
    return (2 * mp_body * plen + attn_flops(s, plen * (plen + 1) // 2)
            + 2 * s["V"] * d)

