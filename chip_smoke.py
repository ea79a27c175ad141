"""Smoke run of the routed serving path on a TPU.

    python chip_smoke.py             # one chip: the served path + its checks
    python chip_smoke.py --chips 4   # four chips: mesh-sharded routing parity

One chip: the six-endpoint pool of ``repro.launch.serve`` behind a streaming
``OmniRouter(RetrievalPredictor)``, with endpoint 0 (h2o-danube-3-4b, the
cheapest router column) at its published widths in bf16 and the other five
at smoke size.  Long-prompt requests are predicted, window-solved, admitted,
prefilled, decoded in paged chunks and folded back under PageSan, LedgerSan
and SolveCert.  Then, on the chip:

* every endpoint's allocator drains pristine;
* the Pallas retrieval-vote kernel agrees with the jnp reference on the
  served store (equal neighbour indices, votes within ``VOTE_ATOL``);
* each paged greedy token of one full-width request is the dense
  ``prefill`` + ``decode_step`` argmax, teacher-forced on the same tokens,
  up to ``LOGIT_TOL`` (bf16 near-ties).

``--chips 4`` runs only a multi-window ``OmniRouter.route_window`` stream
with ``shards=4`` under a four-device query mesh against the same stream
blocked on one device.

The script runs in one process and starts none.  It exits nonzero, before
printing any result, when JAX finds no TPU.  Its last stdout line is one
JSON object naming the device.  Random weights come from fixed seeds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis import sanitize  # noqa: E402
from repro.common import count_params, query_mesh, query_rules, use_mesh  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.core import OmniRouter, RetrievalPredictor, RouterConfig  # noqa: E402
from repro.core.control import StreamController  # noqa: E402
from repro.core.features import FEAT_LEN, featurize_tokens  # noqa: E402
from repro.data import tokenizer  # noqa: E402
from repro.data.qaserve import generate  # noqa: E402
from repro.kernels.topk_retrieval.ops import retrieval_vote  # noqa: E402
from repro.launch.serve import POOL_ARCHS, build_server, use_compile_cache  # noqa: E402
from repro.models.zoo import pad_cache  # noqa: E402
from repro.serving.engine import Request  # noqa: E402

N_REQUESTS = 40      # two routing windows: the first takes half the 48 slots
PROMPT_LEN = 257     # prompt tokens; the last one feeds the first decode step
MAX_NEW = 32
T_MAX = 320          # >= 256 prefilled + 32 decoded positions, page multiple
MAX_CONCURRENCY = 8
ALPHA = 0.5          # quality target low enough that column 0 takes traffic
MIN_FULL = 8         # requests the full-width endpoint must serve
VOTE_ATOL = 1e-4     # votes are means of <= k labels of magnitude <= 1024
LOGIT_TOL = 0.125    # bf16 logits of a random-weight model: near-tie slack
LAM_RTOL = 1e-5      # λ parity of the mesh-sharded stream


def dataset(seed: int = 0):
    ds = generate(n=1200, seed=seed)
    train, _, test = ds.split()
    return train, test


def served_phase(cfgs, *, n_requests=N_REQUESTS, prompt_len=PROMPT_LEN,
                 max_new=MAX_NEW, t_max=T_MAX,
                 max_concurrency=MAX_CONCURRENCY, alpha=ALPHA,
                 min_full=MIN_FULL):
    """Serve ``n_requests`` routed requests through the pool built from
    ``cfgs`` under the sanitizers; check completion, the traffic endpoint 0
    took, and that every allocator drained.  Returns (server, completed
    requests by rid, predictor, test split)."""
    train, test = dataset()
    test = test.subset(np.arange(n_requests))
    predictor = RetrievalPredictor(k=8).fit(train)
    router = OmniRouter(predictor, RouterConfig(alpha=alpha), name="ECCOS-R")
    with sanitize.enabled("pagesan", "ledgersan", "solvecert"):
        server = build_server(cfgs, router, max_concurrency=max_concurrency,
                              t_max=t_max, stream=True, horizon=test.n)
        vocab_cfg = min(cfgs, key=lambda c: c.vocab_size)
        for i in range(test.n):
            toks = tokenizer.encode_for_config(vocab_cfg, test.queries[i], 64)
            server.submit(Request(rid=i, tokens=np.resize(toks, prompt_len),
                                  max_new=max_new))
        done = server.run(lambda batch: test.subset(
            np.array([r.rid for r in batch])))
        for ep in server.endpoints:
            ep.alloc.san.assert_drained(ep)
    done = sorted(done, key=lambda r: r.rid)
    assert len(done) == test.n, (len(done), test.n)
    assert not any(r.failed for r in done), [r.rid for r in done if r.failed]
    assert all(len(r.output) == max_new for r in done)
    n_full = sum(r.endpoint == 0 for r in done)
    assert n_full >= min_full, f"endpoint 0 served {n_full} < {min_full}"
    return server, done, predictor, test


def retrieval_check(predictor, queries, atol=VOTE_ATOL):
    """Pallas retrieval vote vs the jnp reference on the predictor's store:
    identical neighbour indices, votes within ``atol``.  Returns the max
    |vote difference| and the number of queries."""
    emb, labels, n_valid, proj = predictor.device_inputs()
    q = featurize_tokens(jnp.asarray(tokenizer.encode_batch(queries, FEAT_LEN)),
                         proj)
    _, i_k, v_k = retrieval_vote(emb, labels, q, predictor.k, n_valid=n_valid,
                                 use_kernel=True)
    _, i_r, v_r = retrieval_vote(emb, labels, q, predictor.k, n_valid=n_valid,
                                 use_kernel=False)
    i_k, i_r = np.asarray(i_k), np.asarray(i_r)
    assert np.array_equal(i_k, i_r), np.argwhere(i_k != i_r)[:8]
    diff = float(np.max(np.abs(np.asarray(v_k) - np.asarray(v_r))))
    assert diff <= atol, diff
    return diff, len(queries)


def paged_vs_dense(ep, req, tol=LOGIT_TOL):
    """Teacher-force the dense ``prefill`` + ``decode_step`` path of the
    endpoint's model on the request's prompt and its paged greedy output:
    at every step the paged token must be the dense argmax up to ``tol``
    logits.

    The dense decode runs at the endpoint's decode shape — ``ep.L`` rows
    (each holding this request) over ``ep.t_max`` positions — so both paths
    multiply matrices of one shape and only the cache layout differs.  At
    one row the matmuls round differently, and 24 random-weight layers
    amplify that to logit gaps of order one.  The prompt (less its last
    token) must be a page multiple, so the engine's bucketed prefill is this
    prefill.  Returns (steps whose dense argmax equals the paged token,
    steps, max logit gap)."""
    model, params, cfg = ep.model, ep.params, ep.cfg
    toks = np.asarray(req.tokens, np.int32)
    out = np.asarray(req.output, np.int32)
    assert (len(toks) - 1) % ep.page_size == 0, len(toks)
    cache, _ = jax.jit(model.prefill)(params, jnp.asarray(toks[None, :-1]))
    cache = pad_cache(jax.tree.map(
        lambda a: jnp.repeat(a, ep.L, axis=1) if a.ndim == 5 else a, cache),
        ep.t_max)
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    feed = np.concatenate([toks[-1:], out[:-1]])
    agree, gap = 0, 0.0
    for j, tok in enumerate(feed):
        cache, logits = decode(params, cache,
                               jnp.full((ep.L, 1), tok, jnp.int32))
        lg = np.asarray(logits[0, :cfg.vocab_size], np.float32)
        agree += int(lg.argmax() == out[j])
        gap = max(gap, float(lg.max() - lg[out[j]]))
    assert gap <= tol, (gap, tol)
    return agree, len(out), gap


def mesh_parity(n_dev: int, *, alpha=ALPHA, lam_rtol=LAM_RTOL,
                windows=((0, 37), (37, 53), (90, 30))):
    """A ``route_window`` stream over ragged windows with ``shards=n_dev``
    under an ``n_dev``-device query mesh vs the same stream blocked on one
    device: equal assignments, equal ledger, λ within ``lam_rtol``.
    Returns the max relative λ difference."""
    train, test = dataset()
    predictor = RetrievalPredictor(k=8).fit(train)
    loads = np.full(test.m, 50.0)
    counts = np.zeros(test.m)

    def stream():
        router = OmniRouter(predictor, RouterConfig(alpha=alpha,
                                                    shards=n_dev))
        ctrl = StreamController(router, horizon=test.n)
        xs = [ctrl.route(test.subset(np.arange(i0, i0 + n)), loads, counts)
              for i0, n in windows]
        return xs, ctrl.state

    with use_mesh(query_mesh(n_dev), query_rules()):
        x_mesh, st_mesh = stream()
    x_one, st_one = stream()
    for w, a, b in zip(windows, x_mesh, x_one):
        assert np.array_equal(a, b), w
    for f in ("budget_spent", "sr_deficit", "steps"):
        assert np.array_equal(np.asarray(getattr(st_mesh, f)),
                              np.asarray(getattr(st_one, f))), f
    rel = 0.0
    for f in ("lam", "lam_load"):
        a = np.asarray(getattr(st_mesh, f), np.float64)
        b = np.asarray(getattr(st_one, f), np.float64)
        rel = max(rel, float(np.max(np.abs(a - b) / (1e-6 + np.abs(b)))))
    assert rel <= lam_rtol, rel
    return rel


class _CompileWatch:
    """Counts compile-cache hits/misses and sums backend compile time."""

    def __init__(self):
        self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


def _one_chip():
    cfgs = [get_config(POOL_ARCHS[0])] + [get_smoke_config(a)
                                          for a in POOL_ARCHS[1:]]
    t0 = time.perf_counter()
    server, done, predictor, test = served_phase(cfgs)
    print(f"served {len(done)}/{test.n} requests ({PROMPT_LEN}-token prompts, "
          f"{MAX_NEW} new tokens) in {time.perf_counter() - t0:.1f}s: "
          f"{server.windows} streaming windows, {server.dual_iters} dual "
          f"iters")
    for j, ep in enumerate(server.endpoints):
        width = "full width" if j == 0 else "smoke width"
        n_j = sum(r.endpoint == j for r in done)
        print(f"  endpoint {j} {ep.cfg.name} ({width}, "
              f"{count_params(ep.model.decls())} params): {n_j} requests, "
              f"{ep.decoded_tokens} tokens decoded, "
              f"compile_count={ep.compile_count()}")
    print(f"pagesan: {len(server.endpoints)} endpoints drained pristine; "
          f"solvecert certificates: {sanitize.counters['certs']}, "
          f"ledger checks: {sanitize.counters['checks']}")
    diff, nq = retrieval_check(predictor, test.queries)
    print(f"retrieval_vote kernel vs jnp reference: {nq} queries, k="
          f"{predictor.k}, store {predictor.vstore.size} rows: neighbour "
          f"indices equal, max |vote diff| {diff!r} (atol {VOTE_ATOL})")
    req = next(r for r in done if r.endpoint == 0)
    agree, steps, gap = paged_vs_dense(server.endpoints[0], req)
    print(f"paged vs dense ({cfgs[0].name}, request {req.rid}): {agree}/"
          f"{steps} paged tokens are the dense argmax, max logit gap "
          f"{gap!r} (tol {LOGIT_TOL})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh-sharded routing parity phase")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found {dev.platform!r} "
                 f"({dev.device_kind}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    cache_dir = use_compile_cache()
    watch = _CompileWatch()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    if args.chips == 4:
        rel = mesh_parity(4)
        print(f"mesh parity (shards=4, 4 devices vs 1): assignments and "
              f"ledger equal over 3 windows, max relative λ diff {rel!r} "
              f"(rtol {LAM_RTOL})")
    else:
        _one_chip()
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"compile cache: {cache_dir}: {watch.hits} hits, {watch.misses} "
          f"misses; backend compile time {watch.compile_s:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
