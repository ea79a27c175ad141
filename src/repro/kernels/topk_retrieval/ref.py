"""Oracles for fused cosine-similarity top-k retrieval (+ neighbour vote).

Two layers of reference:

- ``topk_retrieval_ref`` / ``retrieval_vote_ref`` — jit-compiled jnp
  references (``jax.lax.top_k`` + masked gather-mean).  They implement the
  same contract as the Pallas kernels (k may exceed the store; empty slots
  are (NEG_INF, -1) and excluded from the vote) and double as the
  device-resident fallback on backends without Pallas TPU lowering.
- ``retrieval_vote_oracle`` — plain NumPy, loop-free but deliberately
  kernel-idiom-free (stable argsort), the ground truth for both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import NEG_INF


def _masked_sims(store, queries, n_valid):
    # full-f32 products on every backend (a TPU's default f32 matmul rounds
    # operands to bf16): the kernel uses the same precision, so near-tied
    # neighbours rank alike on both paths
    sims = jnp.matmul(queries.astype(jnp.float32), store.astype(jnp.float32).T,
                      precision=jax.lax.Precision.HIGHEST)
    if n_valid is not None:
        col = jax.lax.broadcasted_iota(jnp.int32, sims.shape, 1)
        sims = jnp.where(col < n_valid, sims, NEG_INF)
    return sims


def _pad_cols(x, pad: int, fill):
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=fill) if pad else x


def topk_retrieval_ref(store, queries, k: int, n_valid=None):
    """store (N_db, d) L2-normalized; queries (B, d). Returns (vals, idx).

    Handles k > N_db (the seed crashed in ``jax.lax.top_k``): extra slots
    come back as (NEG_INF, -1), matching the kernel contract.
    """
    n_db = store.shape[0]
    k_eff = min(k, n_db)
    vals, idx = jax.lax.top_k(_masked_sims(store, queries, n_valid), k_eff)
    valid = vals > NEG_INF * 0.5
    idx = jnp.where(valid, idx, -1)
    vals = jnp.where(valid, vals, NEG_INF)
    return _pad_cols(vals, k - k_eff, NEG_INF), _pad_cols(idx, k - k_eff, -1)


def retrieval_vote_ref(store, labels, queries, k: int, n_valid=None):
    """Fused-in-one-jit reference for the vote kernel: sim → top-k → label
    gather → mean over valid neighbours.  Returns (vals, idx, votes)."""
    vals, idx = topk_retrieval_ref(store, queries, k, n_valid)
    valid = (idx >= 0)[..., None].astype(jnp.float32)        # (B, k, 1)
    gathered = jnp.asarray(labels, jnp.float32)[jnp.maximum(idx, 0)] * valid
    n_nb = jnp.maximum(valid.sum(axis=1), 1.0)               # (B, 1)
    return vals, idx, gathered.sum(axis=1) / n_nb


def retrieval_vote_oracle(store, labels, queries, k: int, n_valid=None):
    """NumPy ground truth (stable sort ⇒ ties break to the lower db index,
    the same order as ``jax.lax.top_k`` and the kernel fold)."""
    store = np.asarray(store, np.float32)
    labels = np.asarray(labels, np.float32)
    queries = np.asarray(queries, np.float32)
    nv = store.shape[0] if n_valid is None else int(n_valid)
    b = queries.shape[0]
    k_eff = min(k, nv)

    sims = queries @ store[:nv].T                            # (B, nv)
    order = np.argsort(-sims, axis=1, kind="stable")[:, :k_eff]
    vals = np.take_along_axis(sims, order, axis=1)

    votes = labels[order].mean(axis=1) if k_eff else np.zeros(
        (b, labels.shape[1]), np.float32)
    pad = k - k_eff
    vals = np.concatenate([vals, np.full((b, pad), NEG_INF, np.float32)], 1)
    idx = np.concatenate([order, np.full((b, pad), -1)], 1).astype(np.int32)
    return vals, idx, votes.astype(np.float32)
