"""Architecture families: a configuration's ``family`` key alone chooses the
member's weights, parameter tree, reference and work counts.

The pins below were recorded with the harness as it stood before families
existed (commit 66b2f9f: ``bench.weights.make``, ``bench.reference.logits``
and ``bench.flops``), on the CPU, by the steps these tests repeat: each
configuration of ``BENCHMARK.json`` at the program's smoke sizes
(``pool.main_sizes`` of the smoke member), seed ``SEED``; the weights'
SHA-256 over their leaves in sorted order (name, shape, dtype, bytes); the
fp32 reference and its float8 control over the row ``check.pack`` builds
from ``served()`` (``data/dense_gqa_pins.npz``).  The work counts are in
``test_reduce.py``.
"""
import hashlib
import json
import os
import sys
import textwrap

import numpy as np
import pytest

from bench import check, families, pool, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = (1 << 32) + 12345

DIGEST = {
    "omni6-danube3-4b":
        "0c3571478f62b552a8a7406ec2ca1ce281042ea55a786cf7bd8348f141923c56",
    "omni6-internlm2-20b-d8":
        "1c7523cd7326bca7a76f410d6d74ea4594de25f31e27eb3c404db0d0ed211651",
}


def configs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {c["name"]: c["file"] for c in json.load(f)["configs"]}


def config(name):
    with open(os.path.join(ROOT, configs()[name])) as f:
        return json.load(f)


def smoke_sizes(cfg):
    mcfg = pool.model_configs(cfg, smoke_main=True)[cfg["main_column"]]
    return pool.main_sizes(cfg, mcfg), mcfg


def served():
    rs = np.random.RandomState(1234)
    return [check.Served(rs.randint(2, 512, size=p).astype(np.int32),
                         rs.randint(2, 512, size=o).astype(np.int32))
            for p, o in ((10, 6), (7, 8))]


def digest(w):
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        h.update(f"{k}:{a.shape}:{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_every_configuration_is_pinned():
    assert sorted(configs()) == sorted(DIGEST)


@pytest.mark.parametrize("name", sorted(DIGEST))
def test_drawn_weights_are_pinned(name):
    ms, _ = smoke_sizes(config(name))
    assert digest(weights.make(ms, SEED)) == DIGEST[name]


@pytest.mark.parametrize("lowp", [False, True], ids=["fp32", "control"])
@pytest.mark.parametrize("name", sorted(DIGEST))
def test_reference_logits_are_pinned(name, lowp):
    cfg = config(name)
    ms, _ = smoke_sizes(cfg)
    tokens, seg, pos, _ = check.pack(served(), SEED, n_pos=32)
    got = np.asarray(families.load(cfg).logits(
        ms, weights.make(ms, SEED), tokens, seg, pos, lowp=lowp))
    want = np.load(os.path.join(HERE, "data", "dense_gqa_pins.npz"))[
        f"{name}.{'control' if lowp else 'fp32'}"]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("cfg", [{}, {"family": None},
                                 {"family": "no_such_family"},
                                 {"family": "../dense_gqa"},
                                 {"family": "Dense_GQA"}])
def test_missing_or_unknown_family_is_an_error(cfg):
    with pytest.raises(ValueError):
        families.load(cfg)


SPY = '''
"""A family that exists only as this file: the dense family, recording
which of its parts the harness calls."""
from bench import families

_dense = families.load({"family": "dense_gqa"})
CALLS = []


class _Fields(dict):
    def items(self):
        CALLS.append("FIELDS")
        return super().items()


FIELDS = _Fields(_dense.FIELDS)


def _spy(name):
    def call(*a, **k):
        CALLS.append(name)
        return getattr(_dense, name)(*a, **k)
    return call


for _name in ("main_sizes", "sizes", "shapes", "scale", "program_tree",
              "logits", "decode_chunk", "prefill", "step_floor_bytes"):
    globals()[_name] = _spy(_name)
'''


@pytest.fixture
def spy(tmp_path, monkeypatch):
    """``bench.families.spy_family``, found on the package's path."""
    (tmp_path / "spy_family.py").write_text(textwrap.dedent(SPY))
    monkeypatch.setattr(families, "__path__",
                        list(families.__path__) + [str(tmp_path)])
    yield
    sys.modules.pop("bench.families.spy_family", None)


def test_a_new_family_module_is_used_through_load(spy, monkeypatch):
    """The pool, the check, the trace context and the roofline and MFU
    readers reach the member's family only through ``families.load``."""
    from bench import run, trace
    from bench.metrics import decode_mfu, decode_step_roofline, prefill_mfu
    cfg = dict(config("omni6-danube3-4b"), family="spy_family",
               stand_in_columns=[], main_column=0)
    fam = families.load(cfg)
    assert fam.__name__ == "bench.families.spy_family"
    calls = fam.CALLS

    # the full-width ModelConfig takes the family's fields
    assert pool.model_configs(cfg)[0].d_model == cfg["hidden_size"]
    assert calls == ["FIELDS"]
    ms, mcfg = smoke_sizes(cfg)
    assert ms["family"] == "spy_family"
    monkeypatch.setattr(pool, "router", lambda cfg, history: None)
    pool.build(cfg, [mcfg], seed=SEED, history=None)
    assert {"main_sizes", "sizes", "shapes", "scale",
            "program_tree"} <= set(calls)

    del calls[:]
    check.logit_gap(ms, SEED, served())
    check.control_gap(ms, SEED, served())
    assert calls.count("logits") == 3 and "sizes" in calls

    del calls[:]
    win = type("Win", (), {"chunks": [([10, 20], [4, 1], 8)],
                           "prefills": [7]})()
    events = trace.read(os.path.join(HERE, "data", "tiny_trace.json"))
    ctx = run.TraceContext(events, win, ms, "TPU v5 lite", 8)
    assert ctx.family is fam and calls == ["sizes", "step_floor_bytes"]
    for reader in (decode_mfu, decode_step_roofline, prefill_mfu):
        assert reader.read(ctx) > 0
    assert calls.count("decode_chunk") == 2 and calls.count("prefill") == 1
