"""Decode attention entry points (jit wrappers).

``decode_attention`` is the dense split-KV kernel plus its logsumexp merge.
``paged_decode_attention`` / ``paged_verify_attention`` are the serving
plane's: one launch over the stacked page pools with block-table
indirection, interpreted off the TPU.  ``reads_pages_in_place`` is the rule
the model's paged attention selects them by (``models.attention``): the
backend and the width of a page row.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import (decode_attention_kernel, paged_decode_attention_kernel,
                     paged_verify_attention_kernel)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# The narrowest page row (K·D elements: one position, every KV head) for
# which the paged kernel beats XLA's gather of the block table on a TPU v5e:
# at the benchmark's slot geometries the crossover lies between rows of 32
# and 64 elements, below which a grid step moves too few bytes to pay for
# itself (PERF.md §6).
MIN_KERNEL_ROW = 64


def reads_pages_in_place(kv_heads: int, head_dim: int) -> bool:
    """Whether paged attention over pages of ``kv_heads`` x ``head_dim``
    runs the paged kernel, which reads only the live pages where they lie:
    on a TPU, for a page row at least ``MIN_KERNEL_ROW`` wide.  Otherwise
    XLA gathers each slot's block-table row into a dense view."""
    return on_tpu() and kv_heads * head_dim >= MIN_KERNEL_ROW


def merge_partials(o, m, l):
    """Merge per-split (o·l-normalized numerators, m, l) over the split axis.

    o: (B,K,S,G,D); m/l: (B,K,S,G). The identical formula merges cross-device
    partials in the sequence-sharded decode path.  Fully-masked splits carry
    m = NEG_INF and are annihilated by the exp correction.
    """
    m_glob = m.max(axis=2, keepdims=True)                   # (B,K,1,G)
    corr = jnp.exp(m - m_glob)
    l_glob = (l * corr).sum(axis=2)                         # (B,K,G)
    o_glob = (o * corr[..., None]).sum(axis=2)              # (B,K,G,D)
    return o_glob / jnp.maximum(l_glob, 1e-30)[..., None]


@partial(jax.jit, static_argnames=("window", "bs", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     bs: int = 512, interpret: bool | None = None):
    """pos: scalar valid length, or per-sequence (B,) lengths."""
    if interpret is None:
        interpret = not on_tpu()
    b, _, h, d = q.shape
    o, m, l = decode_attention_kernel(q, k_cache, v_cache, pos,
                                      window=window, bs=bs,
                                      interpret=interpret)
    out = merge_partials(o, m, l)                           # (B,K,G,D)
    return out.reshape(b, 1, h, d).astype(q.dtype)


@partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_table, lens, layer, *,
                           window: int = 0, interpret: bool | None = None):
    """q: (B,1,H,D); stacked pools (L, n_pages, PS, K, D); block_table (B, P)
    int32; lens (B,) int32 valid lengths; ``layer`` the pools' layer to
    read.  Returns (B,1,H,D): one Pallas launch that reads the live pages in
    place (interpreted off the TPU)."""
    if interpret is None:
        interpret = not on_tpu()
    return paged_decode_attention_kernel(q, k_pool, v_pool, block_table, lens,
                                         layer, window=window,
                                         interpret=interpret)


@partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify_attention(q, k_pool, v_pool, block_table, lens, layer, *,
                           window: int = 0, interpret: bool | None = None):
    """Speculative verify: q (B,S,H,D) — S query positions per sequence,
    query s of sequence b masked to cache positions < lens[b] + s.
    Returns (B,S,H,D); the same launch and body as the decode op."""
    if interpret is None:
        interpret = not on_tpu()
    return paged_verify_attention_kernel(q, k_pool, v_pool, block_table, lens,
                                         layer, window=window,
                                         interpret=interpret)
