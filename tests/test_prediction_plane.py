"""Device-resident hybrid prediction plane (ISSUE 2): fused retrieval-vote
kernel parity (incl. the seed's two crash cases), on-device featurization,
the ECCOS-H blend, the incremental VectorStore, online fold-back, and the
single-jit featurize→retrieve→vote→solve route path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import tracing
from repro.core import (HybridConfig, HybridPredictor, OmniRouter,
                        PredictorConfig, RetrievalPredictor, RouterConfig,
                        TrainedPredictor, VectorStore, featurize,
                        featurize_tokens, projection)
from repro.data import tokenizer
from repro.kernels.topk_retrieval.kernel import (NEG_INF,
                                                 retrieval_vote_kernel,
                                                 topk_retrieval_kernel)
from repro.kernels.topk_retrieval.ref import (retrieval_vote_oracle,
                                              retrieval_vote_ref,
                                              topk_retrieval_ref)

KEY = jax.random.PRNGKey(0)


def _unit_rows(key, shape):
    x = jax.random.normal(key, shape)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


# --- topk kernel: the seed's crash cases -------------------------------------

@pytest.mark.parametrize("ndb,d,b,k,tile,bq", [
    (700, 64, 17, 8, 512, 64),     # store not a tile multiple (seed crashed)
    (900, 32, 33, 4, 256, 32),     # non-multiple store + padded query block
    (5, 32, 4, 8, 128, 64),        # k > n_db (seed crashed in top_k/fold)
    (128, 16, 3, 128, 64, 64),     # k == n_db across tiles
])
def test_topk_kernel_crash_cases_match_ref(ndb, d, b, k, tile, bq):
    st = _unit_rows(KEY, (ndb, d))
    q = _unit_rows(jax.random.fold_in(KEY, 1), (b, d))
    v1, i1 = topk_retrieval_kernel(st, q, k, bq=bq, tile=tile, interpret=True)
    v2, i2 = topk_retrieval_ref(st, q, k)
    assert v1.shape == (b, k) and i1.shape == (b, k)
    assert float(jnp.max(jnp.abs(v1 - v2))) < 1e-5
    assert float((jnp.sort(i1, 1) == jnp.sort(i2, 1)).mean()) > 0.999
    if k > ndb:                    # empty slots: (NEG_INF, -1), never row 0
        assert bool(jnp.all(i1[:, ndb:] == -1))
        assert bool(jnp.all(v1[:, ndb:] <= NEG_INF * 0.5))


def test_topk_kernel_tie_ordering():
    """Duplicate store rows: ties must resolve to the lower db index, exactly
    like jax.lax.top_k (stable order is what makes the vote deterministic)."""
    base = _unit_rows(KEY, (8, 16))
    st = jnp.concatenate([base, base], axis=0)       # every row duplicated
    q = _unit_rows(jax.random.fold_in(KEY, 2), (5, 16))
    v1, i1 = topk_retrieval_kernel(st, q, 6, bq=8, tile=8, interpret=True)
    v2, i2 = topk_retrieval_ref(st, q, 6)
    assert bool(jnp.all(i1 == i2))                   # exact order, not a set
    assert float(jnp.max(jnp.abs(v1 - v2))) < 1e-6


def test_topk_kernel_dynamic_n_valid():
    """n_valid restricts search to a store prefix without recompiling — the
    contract the growing VectorStore relies on."""
    st = _unit_rows(KEY, (256, 32))
    q = _unit_rows(jax.random.fold_in(KEY, 3), (9, 32))
    v1, i1 = topk_retrieval_kernel(st, q, 4, bq=8, tile=64, interpret=True,
                                   n_valid=100)
    v2, i2 = topk_retrieval_ref(st[:100], q, 4)
    assert bool(jnp.all(i1 == i2))
    assert float(jnp.max(jnp.abs(v1 - v2))) < 1e-6


# --- fused vote kernel vs NumPy oracle vs jit reference ----------------------

@pytest.mark.parametrize("ndb,d,b,k,tile,bq,nl", [
    (700, 64, 17, 8, 512, 64, 12),
    (1024, 32, 130, 16, 256, 64, 6),   # padded query block
    (5, 32, 4, 8, 128, 64, 12),        # k > n_db: vote over 5 valid only
    (512, 16, 64, 4, 128, 128, 2),
])
def test_vote_kernel_matches_oracle(ndb, d, b, k, tile, bq, nl):
    st = _unit_rows(KEY, (ndb, d))
    q = _unit_rows(jax.random.fold_in(KEY, 1), (b, d))
    lab = jax.random.uniform(jax.random.fold_in(KEY, 2), (ndb, nl))
    kv, ki, kvote = retrieval_vote_kernel(st, lab, q, k, bq=bq, tile=tile,
                                          interpret=True)
    rv, ri, rvote = retrieval_vote_ref(st, lab, q, k)
    ov, oi, ovote = retrieval_vote_oracle(st, lab, q, k)
    for got, want in ((kvote, ovote), (rvote, ovote)):
        assert float(jnp.max(jnp.abs(jnp.asarray(got) - want))) < 1e-5
    assert bool(jnp.all(ki == oi)) and bool(jnp.all(ri == oi))
    assert float(jnp.max(jnp.abs(kv - ov))) < 1e-5


def test_vote_excludes_empty_slots():
    """k > n_db: the vote denominator is the VALID neighbour count — the seed
    fold aliased empty slots to db row 0's labels."""
    st = _unit_rows(KEY, (3, 16))
    q = st[:1]
    lab = jnp.asarray([[10.0], [20.0], [30.0]])
    _, idx, vote = retrieval_vote_kernel(st, lab, q, 8, bq=8, tile=8,
                                         interpret=True)
    assert bool(jnp.all(idx[0, 3:] == -1))
    assert abs(float(vote[0, 0]) - 20.0) < 1e-4      # mean of all 3, not 8


def test_vote_kernel_dynamic_n_valid():
    st = _unit_rows(KEY, (128, 16))
    q = _unit_rows(jax.random.fold_in(KEY, 4), (6, 16))
    lab = jax.random.uniform(jax.random.fold_in(KEY, 5), (128, 4))
    kv, ki, kvote = retrieval_vote_kernel(st, lab, q, 8, bq=8, tile=32,
                                          interpret=True, n_valid=50)
    ov, oi, ovote = retrieval_vote_oracle(st, lab, q, 8, n_valid=50)
    assert bool(jnp.all(ki == oi))
    assert float(np.max(np.abs(np.asarray(kvote) - ovote))) < 1e-5


# --- featurization: device path vs host oracle, projection cache -------------

def test_featurize_device_matches_host_oracle(qaserve_splits):
    train, _, _ = qaserve_splits
    texts = train.queries[:32]
    host = featurize(texts, d=128, seed=3)
    toks = jnp.asarray(tokenizer.encode_batch(texts, 64))
    dev = np.asarray(featurize_tokens(toks, projection(128, 3)))
    assert np.abs(host - dev).max() < 1e-5
    assert np.allclose(np.linalg.norm(dev, axis=1), 1.0, atol=1e-5)


def test_projection_is_cached():
    """The seed regenerated the (VOCAB, d) Gaussian on every featurize call."""
    assert projection(64, 1) is projection(64, 1)
    p1, p2 = projection(64, 1), projection(64, 2)
    assert not np.allclose(np.asarray(p1), np.asarray(p2))


# --- VectorStore: online append == refit-from-scratch ------------------------

def test_store_online_append_equals_refit(qaserve_splits):
    train, _, test = qaserve_splits
    half = train.n // 2
    full = RetrievalPredictor(k=8).fit(train)
    grown = RetrievalPredictor(k=8).fit(train.subset(np.arange(half)))
    # fold the second half online, in uneven chunks
    for lo in range(half, train.n, 37):
        idx = np.arange(lo, min(lo + 37, train.n))
        grown.observe([train.queries[i] for i in idx], train.correct[idx],
                      train.out_len[idx])
    assert grown.vstore.size == full.vstore.size == train.n
    cap_f, len_f, cost_f = full.predict_arrays(test)
    cap_g, len_g, cost_g = grown.predict_arrays(test)
    assert np.allclose(cap_f, cap_g, atol=1e-6)
    assert np.allclose(len_f, len_g, atol=1e-4)
    assert np.allclose(cost_f, cost_g, atol=1e-8)


def test_store_growth_and_compaction():
    vs = VectorStore(8, 2, capacity=8)
    rng = np.random.RandomState(0)
    for _ in range(5):
        vs.append(rng.randn(7, 8).astype(np.float32), rng.rand(7, 2))
    assert vs.size == 35 and vs.capacity >= 35
    emb_before = np.asarray(vs.emb[:vs.size])
    vs.compact()
    assert vs.capacity == 128                  # tile-aligned envelope
    assert np.array_equal(np.asarray(vs.emb[:vs.size]), emb_before)
    vs.append(rng.randn(200, 8).astype(np.float32), rng.rand(200, 2))
    assert vs.size == 235 and vs.capacity >= 235


# --- ECCOS-H: schema + parity vs hand-composed T/R blend ---------------------

@pytest.fixture(scope="module")
def hybrid(qaserve_splits):
    train, _, _ = qaserve_splits
    return HybridPredictor(PredictorConfig(n_models=train.m)).fit(
        train, steps=60, batch=48)


def test_hybrid_schema_matches_contract(hybrid, qaserve_splits):
    _, _, test = qaserve_splits
    cap, exp_len, cost = hybrid.predict_arrays(test)
    for arr in (cap, exp_len, cost):
        assert arr.shape == (test.n, test.m)
        assert np.isfinite(arr).all()
    assert (cap >= 0).all() and (cap <= 1).all()
    acc = hybrid.eval_accuracy(test)
    assert set(acc) == {"capability_acc", "bucket_exact", "bucket_within1"}


def test_hybrid_blend_matches_hand_composition(hybrid, qaserve_splits):
    """ECCOS-H == w·R + (1−w)·T with w = sigmoid((s̄ − tau)/temp), where s̄
    is the mean valid-neighbour similarity — composed by hand from the T and
    R predictors plus cosine_topk."""
    _, _, test = qaserve_splits
    hcfg = hybrid.hcfg
    cap_t, len_t, _ = hybrid.trained.predict_arrays(test)
    cap_r, len_r, _ = hybrid.retrieval.predict_arrays(test)
    from repro.core.retrieval import cosine_topk
    q = jnp.asarray(featurize(test.queries, hcfg.d_retrieval, hcfg.feat_seed))
    store = hybrid.retrieval.vstore.emb[:hybrid.retrieval.vstore.size]
    vals, _ = cosine_topk(store, q, hcfg.k)
    sbar = np.asarray(vals).mean(axis=1)
    w = 1.0 / (1.0 + np.exp(-(sbar - hcfg.tau) / hcfg.temp))
    cap_h, len_h, _ = hybrid.predict_arrays(test)
    assert np.allclose(cap_h, w[:, None] * cap_r + (1 - w[:, None]) * cap_t,
                       atol=1e-4)
    assert np.allclose(len_h, w[:, None] * len_r + (1 - w[:, None]) * len_t,
                       atol=1e-2)


def test_hybrid_blend_limits(qaserve_splits):
    """tau → ±∞ degenerates to the pure R / pure T predictors."""
    train, _, test = qaserve_splits
    pure_r = HybridPredictor(PredictorConfig(n_models=train.m),
                             HybridConfig(tau=-1e6)).fit(train, steps=5)
    pure_t = HybridPredictor(PredictorConfig(n_models=train.m),
                             HybridConfig(tau=1e6)).fit(train, steps=5)
    cap_rh, len_rh, _ = pure_r.predict_arrays(test)
    cap_r, len_r, _ = pure_r.retrieval.predict_arrays(test)
    assert np.allclose(cap_rh, cap_r, atol=1e-6)
    cap_th, _, _ = pure_t.predict_arrays(test)
    cap_t, _, _ = pure_t.trained.predict_arrays(test)
    assert np.allclose(cap_th, cap_t, atol=1e-6)


# --- the single-jit route path -----------------------------------------------

@pytest.mark.parametrize("kind", ["retrieval", "trained"])
def test_predictor_device_contract(kind, qaserve_splits):
    """All predictors expose the same device contract, and it agrees with
    their host-facing ``predict_arrays``."""
    train, _, test = qaserve_splits
    if kind == "trained":
        pred = TrainedPredictor(PredictorConfig(n_models=train.m))
        pred.fit(train, steps=5, batch=32)
    else:
        pred = RetrievalPredictor(k=8).fit(train)
    toks = jnp.asarray(tokenizer.encode_batch(test.queries, pred.token_len))
    cap, exp_len, cost = pred.predict_device(
        pred.device_inputs(), toks, jnp.asarray(test.input_len, jnp.float32),
        jnp.asarray(test.price_in, jnp.float32),
        jnp.asarray(test.price_out, jnp.float32))
    cap_a, len_a, cost_a = pred.predict_arrays(test)
    assert np.allclose(np.asarray(cap), cap_a, atol=1e-6)
    assert np.allclose(np.asarray(cost), cost_a, atol=1e-8)


def test_route_is_single_jit_no_host_round_trip(hybrid, qaserve_splits):
    """featurize→retrieve→vote→blend→solve traces into ONE jaxpr whose size
    is independent of the batch — no Python loop, no host materialization
    between the predictor and the solver."""
    _, _, test = qaserve_splits
    router = OmniRouter(hybrid, RouterConfig(alpha=0.7, iters=20))
    fused = router._fused_fn("route")
    inputs = hybrid.device_inputs()

    def trace(n):
        toks = jnp.zeros((n, hybrid.token_len), jnp.int32)
        return jax.make_jaxpr(
            lambda inp, t, il, pi, po, av: fused(
                inp, t, il, pi, po, av, jnp.float32(0.7), jnp.float32(0.73)))(
            inputs, toks, jnp.ones((n,)), jnp.ones((test.m,)),
            jnp.ones((test.m,)), jnp.full((test.m,), 8.0))

    small, big = trace(32), trace(256)
    assert len(small.jaxpr.eqns) == len(big.jaxpr.eqns)


def test_omnirouter_routes_hybrid_end_to_end(hybrid, qaserve_splits):
    from repro.core import evaluate_assignment
    _, _, test = qaserve_splits
    router = OmniRouter(hybrid, RouterConfig(alpha=0.7), name="ECCOS-H")
    batch = test.route_batch(np.full(test.m, float(test.n)))
    solve0 = tracing.total("route.solve")
    x = router.route(batch)
    assert x.shape == (test.n,) and x.min() >= 0 and x.max() < test.m
    res = evaluate_assignment(test, x)
    assert res["success_rate"] >= 0.7 - 0.12        # calibration margin
    assert tracing.total("route.solve") > solve0


# --- online fold-back through scheduler and router ---------------------------

def test_scheduler_folds_completions_online(qaserve_splits):
    from repro.core import SchedulerConfig, run_serving
    train, _, test = qaserve_splits
    ret = RetrievalPredictor(k=8).fit(train)
    router = OmniRouter(ret, RouterConfig(alpha=0.7, iters=40))
    size0 = ret.vstore.size
    run_serving(test, router, SchedulerConfig(loads=8, fold_online=True,
                                              fold_chunk=16))
    assert ret.vstore.size == size0 + test.n        # every completion folded
    # the folded store now answers exactly on the served queries (k=1 analog)
    one = RetrievalPredictor(k=1).fit(train)
    one.observe(test.queries, test.correct, test.out_len)
    cap, _, _ = one.predict_arrays(test.subset(np.arange(8)))
    assert np.allclose(cap, test.correct[:8], atol=1e-6)


def test_engine_folds_completed_requests(qaserve_splits):
    """MultiLLMServer folds completed requests through the same
    ``fold_completions`` path as the simulator (labels come from the feature
    producer; no labels -> silent no-op)."""
    from repro.serving.engine import MultiLLMServer, Request
    train, _, test = qaserve_splits
    ret = RetrievalPredictor(k=8).fit(train)
    router = OmniRouter(ret, RouterConfig(alpha=0.7, iters=40))
    srv = MultiLLMServer([], router, batch_size=4, fold_online=True)
    size0 = ret.vstore.size
    srv._fold_buf = [Request(rid=i, tokens=np.zeros(4, np.int32))
                     for i in range(6)]
    srv._fold(lambda reqs: test.subset(np.array([r.rid for r in reqs])),
              force=True)
    assert ret.vstore.size == size0 + 6 and srv.folded == 6

    class NoTruth:
        def __init__(self, queries):
            self.queries = queries
    srv._fold_buf = [Request(rid=0, tokens=np.zeros(4, np.int32))]
    srv._fold(lambda reqs: NoTruth([test.queries[0]]), force=True)
    assert ret.vstore.size == size0 + 6      # nothing to fold, no crash

    # a store-less predictor absorbs nothing -> folded counter stays honest
    tp = TrainedPredictor(PredictorConfig(n_models=train.m))
    tp.fit(train, steps=2, batch=16)
    srv2 = MultiLLMServer([], OmniRouter(tp, RouterConfig(alpha=0.7)),
                          batch_size=4, fold_online=True)
    srv2._fold_buf = [Request(rid=i, tokens=np.zeros(4, np.int32))
                      for i in range(3)]
    srv2._fold(lambda reqs: test.subset(np.array([r.rid for r in reqs])),
               force=True)
    assert srv2.folded == 0 and not srv2._fold_buf


def test_scheduler_fold_off_by_default(qaserve_splits):
    from repro.core import SchedulerConfig, run_serving
    train, _, test = qaserve_splits
    ret = RetrievalPredictor(k=8).fit(train)
    router = OmniRouter(ret, RouterConfig(alpha=0.7, iters=40))
    size0 = ret.vstore.size
    run_serving(test, router, SchedulerConfig(loads=8))
    assert ret.vstore.size == size0
