"""Ahead-of-time compiles of the serving and routing path's Pallas kernels
for a described TPU v5e (one chip of a ``v5e:2x2`` topology), at the widths
the served path runs: the retrieval vote over a 16384-row store, the dual
solver at window sizes either side of one query block, and paged attention
at h2o-danube-3-4b's and internlm2-20b's heads (K=8, D=120 / 128), alone and
inside a decode step.  Nothing runs; the TPU compiler either accepts each
kernel (a ``tpu_custom_call`` in the compiled program) or raises what the
chip would raise.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import (
    decode_attention_kernel, paged_decode_attention_kernel,
    paged_verify_attention_kernel)
from repro.kernels.lagrangian_assign.kernel import (
    assign_step_kernel, fused_dual_solve, shard_stats)
from repro.kernels.topk_retrieval.kernel import (
    retrieval_vote_kernel, topk_retrieval_kernel)

# h2o-danube-3-4b attention widths; page size of the serving engine
B, H, K, PS, P = 8, 32, 8, 16, 20
M = 6                                   # router columns (QAServe fleet)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, I32, BF16 = jnp.float32, jnp.int32, jnp.bfloat16


def test_retrieval_vote_kernel_compiles(one_chip):
    _compile(lambda s, lab, q, nv: retrieval_vote_kernel(
        s, lab, q, 8, interpret=False, n_valid=nv), one_chip,
        ((16384, 256), F32), ((16384, 12), F32), ((128, 256), F32),
        ((), I32))


def test_topk_retrieval_kernel_compiles(one_chip):
    _compile(lambda s, q, nv: topk_retrieval_kernel(
        s, q, 8, interpret=False, n_valid=nv), one_chip,
        ((16384, 256), F32), ((128, 256), F32), ((), I32))


@pytest.mark.parametrize("n", [256, 65536])   # whole-block fori / grid layout
def test_fused_dual_solve_compiles(one_chip, n):
    _compile(lambda a, b, t, loads: fused_dual_solve(
        a, b, t, loads, lr_eff=1.0, lr_load=0.5, bq=256, interpret=False)[:2],
        one_chip, ((n, M), F32), ((n, M), F32), ((), F32), ((M,), F32))


@pytest.mark.parametrize("lblocks", [1, 4])
def test_shard_stats_compiles(one_chip, lblocks):
    _compile(lambda a, b, lam, lam2, nv: shard_stats(
        a, b, lam, lam2, nv, lblocks=lblocks, interpret=False), one_chip,
        ((65536, M), F32), ((65536, M), F32), ((), F32), ((M,), F32),
        ((lblocks,), F32))


def test_assign_step_kernel_compiles(one_chip):
    _compile(lambda c, q, l1, l2: assign_step_kernel(
        c, q, l1, l2, interpret=False), one_chip,
        ((65536, M), F32), ((65536, M), F32), ((), F32), ((M,), F32))


# query heads by head size: h2o-danube-3-4b (G 4) and internlm2-20b (G 6)
HEADS = {120: 32, 128: 48}


@pytest.mark.parametrize("d", [120, 128])
def test_paged_decode_attention_kernel_compiles(one_chip, d):
    pool = (2, 1 + B * P, PS, K, d)
    _compile(lambda q, k, v, bt, ln, i: paged_decode_attention_kernel(
        q, k, v, bt, ln, i, interpret=False), one_chip,
        ((B, 1, HEADS[d], d), BF16), (pool, BF16), (pool, BF16),
        ((B, P), I32), ((B,), I32), ((), I32))


@pytest.mark.parametrize("d", [120, 128])
def test_paged_verify_attention_kernel_compiles(one_chip, d):
    pool = (2, 1 + B * P, PS, K, d)
    _compile(lambda q, k, v, bt, ln, i: paged_verify_attention_kernel(
        q, k, v, bt, ln, i, interpret=False), one_chip,
        ((B, 4, HEADS[d], d), BF16), (pool, BF16), (pool, BF16),
        ((B, P), I32), ((B,), I32), ((), I32))


@pytest.mark.parametrize("n_heads,kh,d", [(32, K, 120), (48, K, 128),
                                           (4, 2, 16)])
def test_paged_decode_step_on_the_chip(one_chip, monkeypatch, n_heads, kh, d):
    """A two-layer ``decode_step_paged`` scan, compiled for the chip.  At the
    members' attention widths it takes the paged kernel and reads the
    stacked pool where it lies: its optimized program makes neither a
    gathered (B, P·PS, K, D) view of a slot's pages nor a copy of one layer
    of the pool.  At a smoke-width stand-in's (K 2, D 16) it gathers."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels.decode_attention import ops
    from repro.models import build_model
    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # the chip's branch
    cfg = dataclasses.replace(
        get_config("h2o-danube-3-4b"), n_layers=2, d_model=256, d_ff=512,
        n_heads=n_heads, n_kv_heads=kh, head_dim=d, vocab_size=512)
    model = build_model(cfg)
    n_pages = 1 + B * P

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(
        lambda: model.empty_paged_state(B, n_pages, PS)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, I32, sharding=one_chip)
    compiled = jax.jit(model.decode_step_paged, donate_argnums=(1,)).lower(
        params, state, i32(B, 1), i32(B, P), i32(B)).compile()
    text = compiled.as_text()
    gathered = r"\[(1,)*%s\]" % ",".join(map(str, (B, P * PS, kh, d)))
    if not ops.reads_pages_in_place(kh, d):
        assert "tpu_custom_call" not in text
        assert re.search(gathered, text)
        return
    assert "tpu_custom_call" in text
    # no array of a gathered view's or a pool layer's shape (unit leading
    # dims aside), and no scratch that could hold one layer of the pool
    assert not re.search(gathered, text)
    assert not re.search(r"\[(1,)*%s\]" % ",".join(
        map(str, (n_pages, PS, kh, d))), text)
    layer_bytes = n_pages * PS * kh * d * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("d", [120, 128])
def test_dense_decode_attention_kernel_compiles(one_chip, d):
    cache = (B, 1024, K, d)
    _compile(lambda q, k, v, pos: decode_attention_kernel(
        q, k, v, pos, interpret=False), one_chip,
        ((B, 1, H, d), BF16), (cache, BF16), (cache, BF16), ((B,), I32))
