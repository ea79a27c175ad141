"""The trace reduction and the roofline arithmetic on small traces.

``data/tiny_trace.json`` is written by hand so that every expected number
can be checked by eye: two chunk programs of the full-width member (one
program id), two stand-in chunks, one prefill, a vote op and host spans.
"""
import os

import pytest

from bench import flops, peaks, trace
from bench import weights as W

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


def test_danube_sizes_and_bytes():
    s = W.sizes(DANUBE)
    # 24 x (3840*3840*2 + 2*3840*960 + 3*3840*10240) + 32000*3840 (head)
    assert flops.matmul_params(s) == 24 * (
        2 * 3840 * 3840 + 2 * 3840 * 960 + 3 * 3840 * 10240) + 32000 * 3840
    assert flops.kv_bytes_per_token(s) == 92160


def test_decode_roofline_cannot_pass_100():
    """At the least bytes and peak bandwidth the share is exactly 100%."""
    s = W.sizes(DANUBE)
    lens, rem = [100, 200, 50], [8, 3, 1]
    f, b = flops.decode_chunk(s, lens, rem, 8)
    p = peaks.peaks("TPU v5 lite")
    t_min = b / p["hbm_bytes_per_s"]
    assert 100.0 * t_min / t_min == 100.0
    # three live steps for slot 1, one for slot 2: 12 slot-steps in all
    assert f == pytest.approx(2 * flops.matmul_params(s) * 12 + sum(
        flops.attn_flops(s, n + i + 1) for n, r in zip(lens, rem)
        for i in range(r)))
    # every step reads the weights once
    assert b > 8 * flops.weight_bytes(s)


def test_prefill_flops_grow_quadratically_in_attention():
    s = W.sizes(DANUBE)
    lin = flops.prefill(s, 2) - flops.prefill(s, 1)
    assert flops.prefill(s, 4) - flops.prefill(s, 3) > lin


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
