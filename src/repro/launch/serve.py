"""Serving launcher: ECCOS/OmniRouter in front of a multi-arch pool.

CPU demo (smoke configs, real models decoding):
  PYTHONPATH=src python -m repro.launch.serve --requests 24 --mode batching

Streaming control plane (ISSUE 5): requests can arrive over time instead
of all at once, and the router can run as a persistent dual controller —
  PYTHONPATH=src python -m repro.launch.serve --arrival poisson \
      --arrival-rate 4 --stream

``build_server`` is the one place the pool and server are assembled; the
launcher and ``chip_smoke.py`` (the full-width endpoint on a TPU) both call
it.  ``use_compile_cache`` places JAX's persistent compilation cache.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Sequence

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.core import (OmniRouter, RetrievalPredictor, RouterConfig)
from repro.data import arrivals, tokenizer
from repro.data.qaserve import generate
from repro.serving.engine import Endpoint, MultiLLMServer, Request

# one pool member per QAServe fleet column (data/qaserve.py DEFAULT_POOL)
POOL_ARCHS = ("h2o-danube-3-4b", "internlm2-20b", "qwen2-72b",
              "gemma3-4b", "hymba-1.5b", "xlstm-350m")

# <checkout>/.jax_cache — git-ignored, fixed, so a later run finds it again
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``DEFAULT_CACHE_DIR``,
    a fixed path — the path is part of what a later run must match to hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def build_server(cfgs: Sequence[ModelConfig], router, *,
                 max_concurrency: int = 4, t_max: int = 128,
                 batch_size: int = 0, stream: bool = False,
                 horizon: int = 0) -> MultiLLMServer:
    """One paged :class:`Endpoint` per config (endpoint ``i`` serves router
    column ``i``, seeded ``i``) behind ``router`` in a
    :class:`MultiLLMServer`."""
    endpoints = [Endpoint(cfg, max_concurrency=max_concurrency, t_max=t_max,
                          seed=i) for i, cfg in enumerate(cfgs)]
    return MultiLLMServer(endpoints, router, batch_size=batch_size,
                          stream=stream, horizon=horizon)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--mode", default="batching", choices=["batching", "streaming"])
    ap.add_argument("--alpha", type=float, default=0.75)
    ap.add_argument("--loads", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--arrival", default="batch",
                    choices=sorted(arrivals.GENERATORS))
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="arrivals per decode step (non-batch processes)")
    ap.add_argument("--stream", action="store_true",
                    help="persistent dual controller: warm-started windows, "
                         "cumulative budget/alpha ledger")
    args = ap.parse_args(argv)
    use_compile_cache()

    ds = generate(n=600, seed=0)
    train, _, test = ds.split()
    test = test.subset(np.arange(min(args.requests, test.n)))

    router = OmniRouter(RetrievalPredictor(k=8).fit(train),
                        RouterConfig(alpha=args.alpha), name="ECCOS-R")
    server = build_server([get_smoke_config(a) for a in POOL_ARCHS], router,
                          max_concurrency=args.loads,
                          batch_size=1 if args.mode == "streaming" else 0,
                          stream=args.stream, horizon=test.n)
    endpoints = server.endpoints

    # remap router tokens into the pool's (smoke-sized) model vocab — the
    # shared helper replaces the old hardcoded `toks % 500` at call sites
    vocab_cfg = min((e.cfg for e in endpoints), key=lambda c: c.vocab_size)
    steps = arrivals.make(args.arrival, test.n, rate=args.arrival_rate, seed=0)
    for i in range(test.n):
        toks = tokenizer.encode_for_config(vocab_cfg, test.queries[i], 32)
        server.submit(Request(rid=i, tokens=toks, max_new=args.max_new),
                      at_step=steps[i])

    t0 = time.time()
    done = server.run(lambda batch: test.subset(
        np.array([r.rid for r in batch])))
    wall = time.time() - t0

    assign = np.array([r.endpoint for r in sorted(done, key=lambda r: r.rid)])
    sr = float(test.correct[np.arange(len(assign)), assign].mean())
    cost = float(test.cost_matrix()[np.arange(len(assign)), assign].sum())
    print(f"served {len(done)}/{test.n} requests in {wall:.1f}s "
          f"({args.mode}, arrival={args.arrival}"
          f"{', streaming dual' if args.stream else ''}); "
          f"routed SR={sr:.3f} cost=${cost:.4f}; "
          f"route overhead {server.route_seconds:.3f}s over "
          f"{server.route_calls} windows"
          + (f", {server.dual_iters} dual iters" if args.stream else ""))
    for j, e in enumerate(endpoints):
        n_j = int((assign == j).sum())
        print(f"  endpoint {j} ({POOL_ARCHS[j]}): {n_j} reqs, "
              f"{e.decoded_tokens} tokens in {e.busy_steps} decode chunks, "
              f"{e.compile_count()} compiles, "
              f"{e.batch_reprefills} batch re-prefills")


if __name__ == "__main__":
    main()
