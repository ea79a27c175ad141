"""The control: the plain reference at float8, in the program's place,
must read as not correct where the served program reads as correct.

At a size a test run holds: a dense GQA member (``bench.families.dense_gqa``)
of 4 layers at width 256, served by the program's paged ``Endpoint`` (bf16)
with ``bench.weights`` laid out by its family, and compared with the fp32
reference as ``bench.check`` does on the chip.
The chip readings at the cells' own sizes are in ``PERF.md``.
"""
import numpy as np
import pytest

from bench import check, families
from bench import weights as W

SMALL = {"arch": "h2o-danube-3-4b", "family": "dense_gqa",
         "num_hidden_layers": 4, "hidden_size": 256, "intermediate_size": 768,
         "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 32,
         "vocab_size": 2048, "rope_theta": 10000.0, "sliding_window": 0,
         "rms_norm_eps": 1e-5, "tie_word_embeddings": False}


def serve_small(seed: int, n_req: int = 4, max_new: int = 24):
    """Serve ``n_req`` prompts on the program's paged endpoint; returns the
    finished requests as ``check.Served``."""
    import dataclasses

    from repro.configs import get_config
    from repro.serving.engine import Endpoint, Request
    fam = families.load(SMALL)
    mcfg = dataclasses.replace(get_config(SMALL["arch"]), **{
        f: SMALL[k] for k, f in fam.FIELDS.items()})
    ep = Endpoint(mcfg, max_concurrency=n_req, t_max=128, seed=seed)
    ep.params = fam.program_tree(W.make(SMALL, seed), mcfg)
    rs = np.random.RandomState(seed)
    reqs = [Request(rid=i, tokens=rs.randint(2, SMALL["vocab_size"],
                                             size=int(rs.randint(20, 60))
                                             ).astype(np.int32),
                    max_new=max_new) for i in range(n_req)]
    for r in reqs:
        ep.admit(r)
    while ep.active_count():
        ep.step()
    return [check.Served(np.asarray(r.tokens), np.asarray(r.output))
            for r in reqs]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_where_the_program_passes(seed):
    served = serve_small(seed)
    program = check.logit_gap(SMALL, seed, served)["logit_gap"]
    control = check.control_gap(SMALL, seed, served)["logit_gap"]
    assert control >= 3 * program, (program, control)

