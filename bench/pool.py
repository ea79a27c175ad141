"""The system under test, assembled from a configuration file: one paged
``Endpoint`` per pool column behind a streaming
``OmniRouter(RetrievalPredictor)`` in a ``MultiLLMServer`` — what
``repro.launch.serve.build_server`` builds, with each endpoint seeded from
the run seed and the full-width member's weights from ``bench.weights``,
laid out by its family (``bench.families``).
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import numpy as np

from bench import families
from bench import weights as W


def model_configs(cfg: dict, *, smoke_main: bool = False):
    """ModelConfigs of the pool, column order.  The full-width member takes
    every number of the configuration file; ``smoke_main`` serves the
    program's smoke config in its place (CPU rehearsal)."""
    from repro.configs import get_config, get_smoke_config
    fields = families.load(cfg).FIELDS
    main = get_smoke_config(cfg["arch"]) if smoke_main else \
        dataclasses.replace(get_config(cfg["arch"]),
                            **{f: cfg[k] for k, f in fields.items()})
    cols = {c["column"]: get_smoke_config(c["arch"])
            for c in cfg["stand_in_columns"]}
    cols[cfg["main_column"]] = main
    return [cols[j] for j in range(len(cols))]


def main_sizes(cfg: dict, mcfg) -> dict:
    """The numbers ``bench.weights`` and the reference take, read back from
    the ModelConfig actually served (smoke or full), by the member's
    family."""
    return families.load(cfg).main_sizes(cfg, mcfg)


def seeded(seed: int, column: int) -> int:
    return int(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 10 + column]
    ).generate_state(1)[0] & 0x7FFFFFFF)


def build(cfg: dict, cfgs, *, seed: int, history):
    """(endpoints, router).  ``history`` is the router's fitted store."""
    from repro.serving.engine import Endpoint

    j0 = cfg["main_column"]
    endpoints = []
    for j, mc in enumerate(cfgs):
        ep = Endpoint(mc, max_concurrency=cfg["slots"], t_max=cfg["t_max"],
                      seed=seeded(seed, j))
        if j == j0:
            want = jax.tree.map(lambda a: (a.shape, a.dtype), ep.params)
            ep.params = None            # free the program's draw first
            gc.collect()
            tree = families.load(cfg).program_tree(
                W.make(main_sizes(cfg, mc), seed), mc)
            got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
            if got != want:
                raise ValueError("bench.weights does not match the served "
                                 "parameter tree")
            ep.params = tree
        endpoints.append(ep)
    return endpoints, router(cfg, history)


def router(cfg: dict, history):
    """The streaming OmniRouter over a retrieval store fitted on
    ``history``."""
    from repro.core import OmniRouter, RetrievalPredictor, RouterConfig
    return OmniRouter(RetrievalPredictor(k=cfg["router_k"]).fit(history),
                      RouterConfig(alpha=cfg["alpha"]), name="ECCOS-R")


def server(endpoints, router, *, horizon: int):
    """A fresh streaming server over the pool (one per window)."""
    from repro.serving.engine import MultiLLMServer
    return MultiLLMServer(endpoints, router, stream=True, horizon=horizon)


def qaserve(pop, pool_rows):
    """The program's route-feature object for a population slice."""
    from repro.data.qaserve import PoolModel, QAServe
    pool = [PoolModel(p["name"], p["skill"], p["verbosity"], p["price_in"],
                      p["price_out"]) for p in pool_rows]
    return QAServe(queries=list(pop.queries), task=pop.task,
                   difficulty=pop.difficulty, input_len=pop.input_len,
                   correct=pop.correct, out_len=pop.out_len, pool=pool,
                   topic=pop.topic)
