"""The system under test, assembled from a configuration file: one paged
``Endpoint`` per pool column behind a streaming
``OmniRouter(RetrievalPredictor)`` in a ``MultiLLMServer`` — what
``repro.launch.serve.build_server`` builds, with each endpoint seeded from
the run seed and the full-width member's weights from ``bench.weights``.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import numpy as np

from bench import weights as W

# configuration-file key -> ModelConfig field of the full-width member
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "vocab_size": "vocab_size", "rope_theta": "rope_theta",
           "sliding_window": "sliding_window", "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tie_embeddings"}


def model_configs(cfg: dict, *, smoke_main: bool = False):
    """ModelConfigs of the pool, column order.  The full-width member takes
    every number of the configuration file; ``smoke_main`` serves the
    program's smoke config in its place (CPU rehearsal)."""
    from repro.configs import get_config, get_smoke_config
    main = get_smoke_config(cfg["arch"]) if smoke_main else \
        dataclasses.replace(get_config(cfg["arch"]),
                            **{f: cfg[k] for k, f in _FIELDS.items()})
    cols = {c["column"]: get_smoke_config(c["arch"])
            for c in cfg["stand_in_columns"]}
    cols[cfg["main_column"]] = main
    return [cols[j] for j in range(len(cols))]


def main_sizes(cfg: dict, mcfg) -> dict:
    """The numbers ``bench.weights`` and the reference take, read back from
    the ModelConfig actually served (smoke or full)."""
    return dict(cfg, num_hidden_layers=mcfg.n_layers, hidden_size=mcfg.d_model,
                intermediate_size=mcfg.d_ff,
                num_attention_heads=mcfg.n_heads,
                num_key_value_heads=mcfg.n_kv_heads, head_dim=mcfg.hd,
                vocab_size=mcfg.vocab_size, rope_theta=mcfg.rope_theta,
                sliding_window=mcfg.sliding_window, rms_norm_eps=mcfg.norm_eps)


def program_tree(w: dict) -> dict:
    """The reference layout in the program's parameter tree (one scanned
    segment of identical attention layers)."""
    layer = {"attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
             "ln1": w["ln1"], "ln2": w["ln2"]}
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "out_embed": w["out_embed"], "segs": [[layer]]}


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
            tree = program_tree(W.make(main_sizes(cfg, mc), seed))
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
