"""The trace reduction on small traces, and the dense family's roofline
arithmetic (``bench.families.dense_gqa``).

``data/tiny_trace.json`` is written by hand so that every expected number
can be checked by eye: two chunk programs of the full-width member (one
program id), two stand-in chunks, one prefill, a vote op and host spans.
"""
import os
from types import SimpleNamespace

import pytest

from bench import families, peaks, trace

DENSE = families.load({"family": "dense_gqa"})

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def tiny():
    return trace.read(os.path.join(HERE, "data", "tiny_trace.json"))


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]


def test_busy_is_the_union_of_module_intervals(tiny):
    lo, hi = trace.window(tiny)
    assert (lo, hi) == (0.0, 1.0)
    # modules cover [0.1,0.3]+[0.25,0.35], [0.5,0.7], [0.8,0.82], [0.9,0.95]
    assert trace.device_busy(tiny, lo, hi) == pytest.approx(0.52)
    assert trace.device_busy(tiny, 0.2, 0.6) == pytest.approx(0.25)


def test_per_program_sums_and_main_member(tiny):
    chunks = trace.programs(tiny, "jit__chunk_fn")
    assert chunks == {7: (2, pytest.approx(0.4)), 9: (1, pytest.approx(0.02))}
    assert trace.main_programs(chunks, floor_s=0.05) == [7]
    assert trace.programs(tiny, "jit_prefill") == {
        3: (1, pytest.approx(0.1))}


def test_idle_gaps_are_named_by_host_spans(tiny):
    gaps = trace.idle_gaps(tiny, 0.0, 1.0, k=3)
    assert gaps[0][0] == "route" and gaps[0][1] == pytest.approx(0.15)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_top_modules_order(tiny):
    top = trace.top_modules(tiny)
    assert top[0] == ["jit__chunk_fn#7", pytest.approx(0.4)]


DANUBE = {"num_hidden_layers": 24, "hidden_size": 3840,
          "intermediate_size": 10240, "num_attention_heads": 32,
          "num_key_value_heads": 8, "head_dim": 120, "vocab_size": 32000}
INTERNLM2 = {"num_hidden_layers": 8, "hidden_size": 6144,
             "intermediate_size": 16384, "num_attention_heads": 48,
             "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 92544}


def ctx(cfg):
    """What a count reads of a reader's context: the member's sizes."""
    return SimpleNamespace(s=DENSE.sizes(cfg))


@pytest.mark.parametrize("cfg, L, d, KD, F, V", [
    (DANUBE, 24, 3840, 960, 10240, 32000),
    (INTERNLM2, 8, 6144, 1024, 16384, 92544)], ids=["danube", "internlm2"])
def test_sizes_and_bytes(cfg, L, d, KD, F, V):
    s = DENSE.sizes(cfg)
    # L x (2 d^2 [q, o; H*D = d] + 2 d*K*D [k, v] + 3 d*F) + V*d (head)
    assert DENSE.matmul_params(s) == L * (
        2 * d * d + 2 * d * KD + 3 * d * F) + V * d
    assert DENSE.kv_bytes_per_token(s) == 2 * L * KD * 2


# Recorded from ``bench.flops`` as it stood before families existed
# (commit 66b2f9f), at each configuration's full widths.
COUNTS = {
    "danube": (DANUBE, 7677918720, (132364369920, 61885017600),
               (7677911040, 551220633600, 3861351997440)),
    "internlm2": (INTERNLM2, 7378513920, (126415798272, 59192422400),
                  (7378501632, 463525969920, 3228754968576)),
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_work_counts_are_pinned(name):
    cfg, wb, chunk, pre = COUNTS[name]
    c = ctx(cfg)
    assert DENSE.weight_bytes(c.s) == wb
    assert DENSE.step_floor_bytes(c) == wb
    assert DENSE.decode_chunk(c, [100, 200, 50, 700], [8, 3, 1, 5],
                              8) == chunk
    assert tuple(DENSE.prefill(c, n) for n in (1, 74, 513)) == pre


def test_decode_roofline_cannot_pass_100():
    """At the least bytes and peak bandwidth the share is exactly 100%."""
    c = ctx(DANUBE)
    s = c.s
    lens, rem = [100, 200, 50], [8, 3, 1]
    f, b = DENSE.decode_chunk(c, lens, rem, 8)
    p = peaks.peaks("TPU v5 lite")
    t_min = b / p["hbm_bytes_per_s"]
    assert 100.0 * t_min / t_min == 100.0
    # three live steps for slot 1, one for slot 2: 12 slot-steps in all
    assert f == pytest.approx(2 * DENSE.matmul_params(s) * 12 + sum(
        DENSE.attn_flops(s, n + i + 1) for n, r in zip(lens, rem)
        for i in range(r)))
    # every step reads the weights once
    assert b > 8 * DENSE.weight_bytes(s)


def test_prefill_flops_grow_quadratically_in_attention():
    c = ctx(DANUBE)
    lin = DENSE.prefill(c, 2) - DENSE.prefill(c, 1)
    assert DENSE.prefill(c, 4) - DENSE.prefill(c, 3) > lin


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
