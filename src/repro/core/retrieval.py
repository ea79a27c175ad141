"""ECCOS-R: retrieval-based predictor (paper §3.1, Eq. 5).

Historical queries live in a :class:`VectorStore` — a device-resident
(capacity, d) embedding buffer plus (capacity, 2M) label buffer [correctness
per model ‖ output length per model] that grows geometrically and appends
via ``lax.dynamic_update_slice`` (no host copy of the store is ever
rebuilt).  For a new query the top-k cosine neighbours vote: predicted
capability / output length are the neighbour means per model.

The whole predict path is ONE jit boundary: tokens → hashed-BoW embedding
(``features.featurize_tokens``) → fused sim → top-k → gather-labels → vote
(``kernels.topk_retrieval.ops.retrieval_vote``; Pallas on TPU, jnp
reference elsewhere) → cost matrix.  Neighbour indices never round-trip to
the host (the seed pulled ``idx`` back and voted with NumPy fancy-indexing).

Because the number of valid rows is a *dynamic* scalar, online appends
(``observe``) reuse one compilation per capacity doubling.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import logical_shard
from repro.data import tokenizer
from repro.data.qaserve import QAServe

from .features import (FEAT_LEN, featurize,  # noqa: F401  (re-export)
                       featurize_tokens, predicted_cost, projection)


@jax.jit
def _append_rows(buf, rows, at):
    return jax.lax.dynamic_update_slice(buf, rows.astype(buf.dtype), (at, 0))


class VectorStore:
    """Incremental device-resident vector store (embeddings + labels).

    ``append`` writes rows on device via dynamic-slice updates; capacity
    doubles geometrically so N appends cost O(log N) reallocations and the
    retrieval kernels recompile only per capacity, not per append.
    ``compact`` trims the buffers back to a tile-aligned envelope of the
    live rows (after bulk deletions/rebuilds).
    """

    def __init__(self, d: int, n_labels: int, capacity: int = 1024):
        self.size = 0
        self.emb = jnp.zeros((max(capacity, 8), d), jnp.float32)
        self.labels = jnp.zeros((max(capacity, 8), n_labels), jnp.float32)

    @property
    def capacity(self) -> int:
        return self.emb.shape[0]

    @property
    def n_valid(self) -> jax.Array:
        """Dynamic row count — feed to the retrieval kernels' n_valid."""
        return jnp.asarray(self.size, jnp.int32)

    def _grow(self, cap: int):
        cap = max(cap, 8)
        self.emb = _append_rows(
            jnp.zeros((cap, self.emb.shape[1]), jnp.float32),
            self.emb[:self.size], 0)
        self.labels = _append_rows(
            jnp.zeros((cap, self.labels.shape[1]), jnp.float32),
            self.labels[:self.size], 0)

    def append(self, emb, labels) -> "VectorStore":
        emb = jnp.asarray(emb, jnp.float32)
        n = emb.shape[0]
        if self.size + n > self.capacity:
            cap = self.capacity
            while cap < self.size + n:
                cap *= 2
            self._grow(cap)
        self.emb = _append_rows(self.emb, emb, self.size)
        self.labels = _append_rows(self.labels, jnp.asarray(labels), self.size)
        self.size += n
        return self

    def compact(self) -> "VectorStore":
        self._grow(-(-max(self.size, 1) // 128) * 128)
        return self


@partial(jax.jit, static_argnames=("k", "use_kernel"))
def retrieval_predict_device(store_emb, store_labels, n_valid, proj, tokens,
                             input_len, price_in, price_out, *, k: int,
                             use_kernel: Optional[bool]):
    """Pure-jax ECCOS-R predict: tokens -> (cap, exp_len, cost, conf).

    ``conf`` is the mean cosine similarity of the valid neighbours — the
    retrieval-confidence signal the hybrid blend consumes.
    """
    from repro.kernels.topk_retrieval.ops import retrieval_vote

    with jax.named_scope("featurize"):
        q = featurize_tokens(tokens, proj)
    with jax.named_scope("vote"):
        vals, idx, votes = retrieval_vote(store_emb, store_labels, q, k,
                                          n_valid=n_valid,
                                          use_kernel=use_kernel)
    m = price_in.shape[0]
    cap, exp_len = votes[:, :m], votes[:, m:]
    cost = predicted_cost(input_len, exp_len, price_in, price_out)
    valid = (idx >= 0).astype(jnp.float32)
    conf = (jnp.where(idx >= 0, vals, 0.0).sum(1)
            / jnp.maximum(valid.sum(1), 1.0))
    return cap, exp_len, cost, conf


@partial(jax.jit, static_argnames=("k",))
def cosine_topk(store: jax.Array, queries: jax.Array, k: int = 8):
    """store (N_db, d) L2-normalized; queries (B, d). Returns (vals, idx).

    Plain two-op XLA path (matmul + top_k), kept as the unfused baseline for
    ``benchmarks.bench_retrieval``.  k is clamped to the store size (the
    seed crashed in ``jax.lax.top_k`` for k > N_db); clamped slots return
    (NEG_INF, -1) like the fused paths.
    """
    from repro.kernels.topk_retrieval.ref import topk_retrieval_ref

    store = logical_shard(store, "db_rows", "db_dim")
    return topk_retrieval_ref(store, queries, k)


class RetrievalPredictor:
    """ECCOS-R over a :class:`VectorStore`, fully device-resident."""

    def __init__(self, d: int = 256, k: int = 8,
                 use_kernel: Optional[bool] = None, seed: int = 7):
        self.d = d
        self.k = k
        self.use_kernel = use_kernel   # None -> Pallas on TPU, jnp elsewhere
        self.seed = seed
        self.vstore: Optional[VectorStore] = None
        self.pool = None

    # --- store construction / online growth -------------------------------
    def _embed_texts(self, texts) -> jax.Array:
        toks = jnp.asarray(tokenizer.encode_batch(texts, FEAT_LEN))
        return featurize_tokens(toks, projection(self.d, self.seed))

    def fit(self, ds: QAServe):
        self.pool = ds.pool
        self.vstore = VectorStore(self.d, 2 * ds.m,
                                  capacity=max(1024, ds.n))
        self.observe(ds.queries, ds.correct, ds.out_len)
        return self

    def observe(self, texts, correct, out_len) -> "RetrievalPredictor":
        """Fold completed requests back into the store online (the
        scheduler / serving engine call this as requests finish)."""
        labels = jnp.concatenate(
            [jnp.asarray(correct, jnp.float32),
             jnp.asarray(out_len, jnp.float32)], axis=1)
        self.vstore.append(self._embed_texts(texts), labels)
        return self

    # --- the device predict contract (shared with Trained/Hybrid) ---------
    @property
    def token_len(self) -> int:
        return FEAT_LEN

    def device_inputs(self):
        vs = self.vstore
        return (vs.emb, vs.labels, vs.n_valid, projection(self.d, self.seed))

    def predict_device(self, inputs, tokens, input_len, price_in, price_out):
        """Pure-jax (traceable) — composes under one outer jit with the
        solver; see ``OmniRouter``."""
        emb, labels, n_valid, proj = inputs
        cap, exp_len, cost, _ = retrieval_predict_device(
            emb, labels, n_valid, proj, tokens, input_len, price_in,
            price_out, k=self.k, use_kernel=self.use_kernel)
        return cap, exp_len, cost

    def predict_arrays(self, ds):
        """Returns (capability (N,M), expected_out_len (N,M), cost (N,M)).

        ``ds`` is anything exposing the RouteBatch feature surface
        (queries, input_len, price_in, price_out): a QAServe or RouteBatch.
        """
        toks = jnp.asarray(tokenizer.encode_batch(ds.queries, FEAT_LEN))
        cap, exp_len, cost = self.predict_device(
            self.device_inputs(), toks, jnp.asarray(ds.input_len, jnp.float32),
            jnp.asarray(ds.price_in, jnp.float32),
            jnp.asarray(ds.price_out, jnp.float32))
        return np.asarray(cap), np.asarray(exp_len), np.asarray(cost)

    def eval_accuracy(self, ds: QAServe, n_buckets: int = 10) -> Dict[str, float]:
        from repro.data.qaserve import bucketize
        cap, exp_len, _ = self.predict_arrays(ds)
        cap_acc = float(((cap > 0.5) == (ds.correct > 0)).mean())
        pred_b = bucketize(exp_len, n_buckets)
        true_b = bucketize(ds.out_len, n_buckets)
        return {"capability_acc": cap_acc,
                "bucket_exact": float((pred_b == true_b).mean()),
                "bucket_within1": float((np.abs(pred_b - true_b) <= 1).mean())}
