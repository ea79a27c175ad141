"""The generator: a seed gives the same inputs; every seed offers the same
set of sizes and arrivals, in another order."""
import json
import os

import numpy as np
import pytest

from bench import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["chat", "burst-short"])
def test_seeds_permute_one_population(mix):
    m = load(f"bench/traffic/{mix}.json")
    cfg = load("bench/configs/omni6-danube3-4b.json")
    kw = dict(pool=cfg["pool"], main_column=0, rate=12.0, seconds=10.0,
              vocab=512)
    a = traffic.make_schedule(m, seed=3_000_000_001, **kw)
    a2 = traffic.make_schedule(m, seed=3_000_000_001, **kw)
    b = traffic.make_schedule(m, seed=7, **kw)
    assert a.n == b.n == 120
    assert np.array_equal(a.due, a2.due)
    assert all(np.array_equal(p, q) for p, q in zip(a.prompts, a2.prompts))
    assert sorted(map(len, a.prompts)) == sorted(map(len, b.prompts))
    assert sorted(a.max_new) == sorted(b.max_new)
    # the gaps are one sequence, rotated: all but the one at the wrap agree
    ga, gb = (np.round(np.diff(x.due), 9) for x in (a, b))
    assert np.isin(gb, ga).sum() >= a.n - 2
    assert not np.array_equal(a.due, b.due)
    assert a.due[-1] < 10.0 and a.due[0] == 0.0
    assert max(len(p) - 1 + n for p, n in zip(a.prompts, a.max_new)) <= \
        cfg["t_max"]
