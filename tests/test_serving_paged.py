"""Paged-KV serving plane: kernel parity over ragged lengths, page allocator
invariants, paged-vs-dense model decode parity, and the engine contract
(identical outputs to the restart baseline with ZERO batch-wide re-prefills
and a constant compile count under churn)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# paged decode-attention kernel vs oracle
# ---------------------------------------------------------------------------

def _paged_case(b, s, h, kh, d, ps, p_max, lens, *, n_layers=1, free=()):
    """q (B,S,H,D) and stacked pools (L, n_pages, PS, K, D) of random
    values, and a block table of shuffled physical ids (page 0 = dump) that
    covers lens + s - 1 of each sequence; rows in ``free`` stay 0, as a
    freed slot's does."""
    n_pages = 1 + b * p_max
    q = jax.random.normal(KEY, (b, s, h, d), jnp.float32)
    pool = (n_layers, n_pages, ps, kh, d)
    kp = jax.random.normal(jax.random.fold_in(KEY, 1), pool, jnp.float32)
    vp = jax.random.normal(jax.random.fold_in(KEY, 2), pool, jnp.float32)
    rng = np.random.RandomState(0)
    bt = np.zeros((b, p_max), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    for i in range(b):
        if i not in free:
            n_used = -(-(int(lens[i]) + s - 1) // ps)
            bt[i, :n_used] = perm[i * p_max: i * p_max + n_used]
    return q, kp, vp, bt, jnp.asarray(lens, jnp.int32)


# lens 0, 1, PS-1, PS, PS+1, one block (8 pages of 16), one block + 1, the
# whole table
EDGE_LENS = (0, 1, 15, 16, 17, 128, 129, 256)


@pytest.mark.parametrize("b,h,kh,d,ps,p_max,window,lens,layer,free", [
    (3, 8, 2, 64, 16, 8, 0, (100, 17, 128), 0, ()),    # GQA, ragged
    (2, 4, 4, 32, 8, 4, 0, (31, 1), 0, ()),            # MHA, non-tile lens
    (2, 8, 2, 64, 16, 8, 24, (100, 77), 0, ()),        # sliding window
    (1, 4, 1, 128, 32, 2, 0, (64,), 0, ()),            # one kv head, full
    (8, 8, 2, 64, 16, 16, 0, EDGE_LENS, 1, ()),        # page / block edges
    (3, 8, 2, 64, 16, 8, 0, (1, 40, 1), 0, (0, 2)),    # freed slots, row 0
    (2, 8, 2, 64, 16, 24, 40, (300, 129), 0, ()),      # window skips blocks
    (2, 32, 8, 120, 16, 6, 0, (70, 96), 2, ()),        # danube: D 120, G 4
    (2, 48, 8, 128, 16, 6, 0, (33, 90), 2, ()),        # internlm2: 128, G 6
    (8, 32, 8, 120, 16, 16, 0, EDGE_LENS, 1, ()),      # danube, edges
    (8, 48, 8, 128, 16, 16, 40, EDGE_LENS, 2, ()),     # internlm2, window
    (8, 4, 2, 16, 16, 16, 0, EDGE_LENS, 2, ()),        # stand-in: K 2, D 16
])
def test_paged_decode_attention_vs_oracle(b, h, kh, d, ps, p_max, window, lens,
                                          layer, free):
    """The paged kernel (interpreted) reads layer ``layer`` of stacked pools
    and agrees with the NumPy oracle and the jnp reference on that layer's
    pool; the jit entry point runs the same kernel."""
    from repro.kernels.decode_attention.kernel import paged_decode_attention_kernel
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import (paged_decode_attention_np,
                                                    paged_decode_attention_ref)
    q, kp, vp, bt, lens = _paged_case(b, 1, h, kh, d, ps, p_max, lens,
                                      n_layers=layer + 1, free=free)
    oracle = paged_decode_attention_np(q, kp[layer], vp[layer], bt,
                                       np.asarray(lens), window=window)
    out_k = paged_decode_attention_kernel(q, kp, vp, jnp.asarray(bt), lens,
                                          layer, window=window, interpret=True)
    out_r = paged_decode_attention_ref(q, kp[layer], vp[layer],
                                       jnp.asarray(bt), lens, window=window)
    out_d = paged_decode_attention(q, kp, vp, jnp.asarray(bt), lens,
                                   jnp.asarray(layer, jnp.int32),
                                   window=window)
    for out in (out_k, out_d):
        assert float(np.max(np.abs(np.asarray(out) - oracle))) < 2e-5
    # the kernel returns zeros for an empty sequence; the reference's
    # all-masked softmax is uniform there, so it is held to the others only
    live = np.asarray(lens) > 0
    assert float(np.max(np.abs(np.asarray(out_r)[live] - oracle[live]))) < 2e-5


@pytest.mark.parametrize("b,s,h,kh,d,ps,p_max,window,lens,layer", [
    (3, 4, 8, 2, 64, 16, 8, 0, (100, 17, 1), 0),     # GQA, ragged + near-empty
    (2, 2, 4, 4, 32, 8, 4, 0, (13, 1), 0),           # MHA, draft from scratch
    (2, 3, 8, 2, 64, 16, 8, 24, (100, 77), 0),       # sliding window
    (2, 4, 8, 2, 64, 16, 24, 40, (126, 300), 1),     # rows cross a block
    # danube widths: lens 0, 1, a page edge, rows across a block edge, the
    # whole table (lens + S - 1 = P·PS)
    (6, 4, 32, 8, 120, 16, 16, 0, (0, 1, 16, 126, 129, 253), 1),
    (4, 3, 48, 8, 128, 16, 16, 24, (1, 17, 127, 254), 2),   # internlm2
    (4, 4, 4, 2, 16, 16, 16, 0, (0, 16, 128, 253), 0),      # stand-in
])
def test_paged_verify_attention_vs_oracle(b, s, h, kh, d, ps, p_max, window,
                                          lens, layer):
    """The speculative-verify kernel (S query positions per sequence, query s
    masked to positions < lens + s) against the per-(sequence, position)
    NumPy oracle — kernel body, jnp reference, and jit entry point."""
    from repro.kernels.decode_attention.kernel import paged_verify_attention_kernel
    from repro.kernels.decode_attention.ops import paged_verify_attention
    from repro.kernels.decode_attention.ref import (paged_verify_attention_np,
                                                    paged_verify_attention_ref)
    q, kp, vp, bt, lens = _paged_case(b, s, h, kh, d, ps, p_max, lens,
                                      n_layers=layer + 1)
    oracle = paged_verify_attention_np(q, kp[layer], vp[layer], bt,
                                       np.asarray(lens), window=window)
    out_k = paged_verify_attention_kernel(q, kp, vp, jnp.asarray(bt), lens,
                                          layer, window=window, interpret=True)
    out_r = paged_verify_attention_ref(q, kp[layer], vp[layer],
                                       jnp.asarray(bt), lens, window=window)
    out_d = paged_verify_attention(q, kp, vp, jnp.asarray(bt), lens,
                                   jnp.asarray(layer, jnp.int32),
                                   window=window)
    for out in (out_k, out_d):
        assert float(np.max(np.abs(np.asarray(out) - oracle))) < 2e-5
    # the reference's all-masked softmax (a position with no cache before
    # it) is uniform where the kernel's is zero: held to the others elsewhere
    live = np.asarray(lens)[:, None] + np.arange(s)[None, :] > 0
    assert float(np.max(np.abs(np.asarray(out_r)[live] - oracle[live]))) < 2e-5
    # S-slice consistency: slice s of the verify op == the decode op at the
    # same position (the decode op's lens convention is the slice's lens + s)
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    for j in range(s):
        one = paged_decode_attention_ref(q[:, j:j + 1], kp[layer], vp[layer],
                                         jnp.asarray(bt), lens + j,
                                         window=window)
        assert np.array_equal(np.asarray(one[:, 0]), np.asarray(out_r[:, j]))


@pytest.mark.parametrize("window", [0, 24])
def test_paged_verify_slice_is_decode_kernel_bitwise(window):
    """On the kernel path too, slice s of verify equals decode at lens + s
    bit for bit (the speculative "greedy == strong-only greedy" guarantee):
    rows, blocks and pages outside a position's span change nothing.  In
    float32: the interpreter's bfloat16 dot on the CPU sums a row in an
    order that depends on the row count, which the chip's does not."""
    from repro.kernels.decode_attention.kernel import (
        paged_decode_attention_kernel, paged_verify_attention_kernel)
    s = 4
    q, kp, vp, bt, lens = _paged_case(3, s, 8, 2, 64, 16, 24, (126, 5, 250),
                                      n_layers=2)
    bt = jnp.asarray(bt)
    ver = paged_verify_attention_kernel(q, kp, vp, bt, lens, 1,
                                        window=window, interpret=True)
    for j in range(s):
        one = paged_decode_attention_kernel(q[:, j:j + 1], kp, vp, bt,
                                            lens + j, 1, window=window,
                                            interpret=True)
        assert np.array_equal(np.asarray(one[:, 0]), np.asarray(ver[:, j]))


@pytest.mark.parametrize("s", [1, 4])
@pytest.mark.parametrize("backend,kh,d,kernel", [
    ("tpu", 8, 120, True),       # h2o-danube3-4b's pages
    ("tpu", 8, 128, True),       # internlm2-20b's
    ("tpu", 2, 16, False),       # a smoke-width stand-in's: too narrow
    ("tpu", 2, 32, True),        # the narrowest row the kernel pays at
    ("cpu", 8, 128, False),      # off a TPU the gather, whatever the width
    ("cpu", 2, 16, False),
])
def test_paged_attention_dispatch_rule(monkeypatch, backend, kh, d, kernel,
                                       s):
    """Paged decode and verify take the kernel on a TPU where the page row
    is wide, and XLA's gather otherwise: the rule reads the backend and the
    pool's shape, nothing else."""
    from repro.kernels.decode_attention import ops
    from repro.models.attention import paged_attention
    monkeypatch.setattr(ops, "on_tpu", lambda: backend == "tpu")
    assert ops.reads_pages_in_place(kh, d) is kernel
    called = []

    def kernel_op(q, *args, **kw):
        called.append(kw)
        return jnp.zeros_like(q)
    monkeypatch.setattr(ops, "paged_decode_attention", kernel_op)
    monkeypatch.setattr(ops, "paged_verify_attention", kernel_op)
    q, kp, vp, bt, lens = _paged_case(2, s, 2 * kh, kh, d, 16, 2, (9, 20),
                                      n_layers=2)
    cache = {"k_pages": kp, "v_pages": vp, "layer": jnp.asarray(1),
             "block_table": jnp.asarray(bt)}
    out = paged_attention(q, cache, lens)
    assert len(called) == int(kernel)
    if not kernel:
        from repro.kernels.decode_attention.ref import paged_verify_attention_np
        want = paged_verify_attention_np(q, kp[1], vp[1], bt, np.asarray(lens))
        assert float(np.max(np.abs(np.asarray(out) - want))) < 2e-5


def test_dense_decode_attention_ragged_and_lens():
    """The seed crashed on t % bs != 0 (`assert t % bs == 0`); the fix
    zero-pads + NEG_INF-masks the ragged tail.  Also covers the (B,) lens
    vector replacing the scalar pos."""
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    b, t, h, kh, d = 2, 700, 8, 2, 64
    q = jax.random.normal(KEY, (b, 1, h, d), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, kh, d), jnp.float32)
    vc = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, kh, d), jnp.float32)
    out = decode_attention(q, kc, vc, 650, bs=512)      # 700 % 512 != 0
    ref = decode_attention_ref(q, kc, vc, 650)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    lens = jnp.asarray([650, 3])
    out = decode_attention(q, kc, vc, lens, bs=256, window=37)
    ref = decode_attention_ref(q, kc, vc, lens, window=37)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------

def test_page_allocator_invariants():
    from repro.serving.engine import PageAllocator
    a = PageAllocator(n_pages=9, n_slots=3)
    got = a.alloc_pages(5)
    assert len(set(got)) == 5 and 0 not in got          # unique, no dump page
    more = a.alloc_pages(3)
    assert not (set(got) & set(more))                   # no double allocation
    with pytest.raises(RuntimeError):
        a.alloc_pages(1)                                # pool exhausted
    a.release_pages(got)
    again = a.alloc_pages(5)
    assert set(again) == set(got)                       # freed pages reused
    s = [a.alloc_slot() for _ in range(3)]
    assert sorted(s) == [0, 1, 2]
    with pytest.raises(RuntimeError, match="slot pool exhausted"):
        a.alloc_slot()                  # descriptive, not a bare IndexError
    a.release_slot(s[0])
    assert a.alloc_slot() == s[0]
    # exception safety: a failing alloc_pages leaves NO partial pops behind
    free_before = list(a.free_pages)
    with pytest.raises(RuntimeError, match="page pool exhausted"):
        a.alloc_pages(len(free_before) + 1)
    assert a.free_pages == free_before


# ---------------------------------------------------------------------------
# paged model decode == dense model decode (per-arch, bit-exact at bf16)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,kv_dtype", [
    ("h2o-danube-3-4b", "bf16"),   # dense GQA + sliding window
    ("h2o-danube-3-4b", "int8"),   # quantized page pools + scale pages
    ("hymba-1.5b", "bf16"),        # hybrid: paged attn KV ∥ per-slot SSM
    ("dbrx-132b", "bf16"),         # MoE FFN (same batch -> same routing)
    ("xlstm-350m", "bf16"),        # no KV at all: per-slot recurrent state
])
def test_paged_decode_matches_dense(arch, kv_dtype):
    """Per-request paged prefill+decode reproduces the packed dense batch
    token-for-token (equal prompt lengths, so the dense path has no pads)."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.models.zoo import (pad_cache, pages_per_request,
                                  prefill_into_pages)
    cfg = dataclasses.replace(get_smoke_config(arch), kv_cache_dtype=kv_dtype)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    B, PS, P = 2, 8, 4
    n_pages = 1 + B * P
    toks = [rng.randint(1, cfg.vocab_size, (11,)).astype(np.int32)
            for _ in range(B)]
    tb = np.stack(toks)

    cache, _ = model.prefill(params, jnp.asarray(tb[:, :-1]))
    cache = pad_cache(cache, P * PS)
    state = model.empty_paged_state(B, n_pages, PS)
    bt = np.zeros((B, P), np.int32)
    next_page = 1
    for b in range(B):
        npg = pages_per_request(10, 6, PS)
        pages = list(range(next_page, next_page + npg))
        next_page += npg
        bt[b, :npg] = pages
        pc, _ = model.prefill(params, jnp.asarray(toks[b][None, :-1]))
        state = prefill_into_pages(state, pc,
                                   np.asarray(pages[:2], np.int32), b, PS)

    last_d = jnp.asarray(tb[:, -1:])
    last_p = last_d
    lens = jnp.asarray([10, 10])
    for _ in range(6):
        cache, ld = model.decode_step(params, cache, last_d)
        state, lp = model.decode_step_paged(params, state, last_p,
                                            jnp.asarray(bt), lens)
        nd = jnp.argmax(ld[:, :cfg.vocab_size], -1)
        np_ = jnp.argmax(lp[:, :cfg.vocab_size], -1)
        assert bool(jnp.all(nd == np_))
        last_d = nd[:, None].astype(jnp.int32)
        last_p = np_[:, None].astype(jnp.int32)
        lens = lens + 1


# ---------------------------------------------------------------------------
# engine: compile count constant under churn; allocator round-trips
# ---------------------------------------------------------------------------

def test_paged_endpoint_compile_count_constant_under_churn():
    from repro.configs import get_smoke_config
    from repro.serving.engine import Endpoint, Request
    ep = Endpoint(get_smoke_config("h2o-danube-3-4b"), max_concurrency=3,
                  t_max=64, page_size=8, sync_every=4, seed=0)
    rng = np.random.RandomState(0)

    def serve(rid, plen, max_new):
        ep.admit(Request(rid=rid, tokens=rng.randint(1, 500, (plen,)),
                         max_new=max_new))
        done = []
        while ep.active_count():
            done += ep.step()
        return done

    # warmup: one request per prompt-length bucket (page multiples of 8)
    serve(0, 11, 3)
    serve(1, 5, 2)
    assert ep.compile_count() > 0   # instrumentation alive, not vacuous
    # churn: varied lengths within the warmed buckets, varied max_new —
    # CompileGuard raises if anything retraces (engine contract from PR 3)
    from repro.common import CompileGuard
    with CompileGuard(ep, label="paged endpoint churn"):
        for rid, (plen, mn) in enumerate([(9, 5), (4, 1), (13, 6), (2, 3),
                                          (16, 2), (7, 7)], start=2):
            (done,) = serve(rid, plen, mn)
            assert len(done.output) == mn
    assert ep.batch_reprefills == 0
    # allocator drained back to full capacity
    assert len(ep.alloc.free_slots) == ep.L
    assert len(ep.alloc.free_pages) == ep.alloc.n_pages - 1


def test_kv_page_counts_match_a_brute_force_count():
    """An endpoint on the kernel path records, per chunk, the KV pages its
    occupied slots attend to (from the host mirrors at dispatch) and the
    pages of every slot's whole block-table row; one off it records
    neither.  Checked against a step-by-step walk of each slot under churn:
    admissions between chunks, slots finishing inside a chunk.  Off a TPU
    the model gathers, which changes nothing the counts see."""
    from repro.common import tracing
    from repro.configs import get_smoke_config
    from repro.serving.engine import Endpoint, Request
    ep = Endpoint(get_smoke_config("h2o-danube-3-4b"), max_concurrency=3,
                  t_max=64, page_size=8, sync_every=4, seed=0)
    ep.label = 0
    rng = np.random.RandomState(0)
    queue = [Request(rid=i, tokens=rng.randint(1, 500, size=n)
                     .astype(np.int32), max_new=m)
             for i, (n, m) in enumerate(((3, 6), (9, 2), (17, 11), (8, 1),
                                         (30, 9), (2, 5), (16, 7)))]
    tracing.reset()
    assert not ep.reads_in_place            # the gather: nothing counted
    ep.admit(queue.pop(0))
    ep.step()
    assert not {name for name, *_ in tracing.samples} & {"kv_pages",
                                                          "kv_pages_table"}
    ep.reads_in_place = True
    mid_chunk = 0
    while queue or ep.active_count():
        while queue and ep.has_capacity():
            ep.admit(queue.pop(0))
        want = 0
        for slot, req in enumerate(ep.slot_req):
            if req is None:
                continue
            n, rem = int(ep.lens[slot]), int(ep.remaining[slot])
            mid_chunk += 0 < rem < ep.sync_every
            for _ in range(ep.sync_every):
                # the step writes position n and reads positions 0..n
                want += sum(page * ep.page_size <= n
                            for page in range(ep.pages_per_slot))
                if rem:
                    n, rem = n + 1, rem - 1
        n_before = len(tracing.samples)
        ep.step()
        got = {name: n for name, label, _, n in
               list(tracing.samples)[n_before:] if label == 0}
        assert got["kv_pages"] == want
        assert got["kv_pages_table"] == 3 * 8 * 4
    assert mid_chunk >= 3
    assert len(ep.alloc.free_slots) == ep.L
    assert len(ep.alloc.free_pages) == ep.alloc.n_pages - 1
    tracing.reset()


@pytest.mark.slow
def test_server_paged_matches_restart_engine():
    """End-to-end MultiLLMServer: the paged engine and the restart baseline
    produce identical outputs (equal prompt lengths, fp32, so the restart
    engine's left-padding is inert) while the paged engine performs ZERO
    batch-wide re-prefills."""
    from repro.configs import get_smoke_config
    from repro.core.baselines import BalanceAware
    from repro.serving.engine import (Endpoint, MultiLLMServer, Request,
                                      RestartEndpoint, null_route_features)

    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 500, (9,)).astype(np.int32) for _ in range(9)]
    outs = {}
    stats = {}
    for name, cls in (("paged", Endpoint), ("restart", RestartEndpoint)):
        eps = [cls(dataclasses.replace(get_smoke_config(a), dtype=jnp.float32),
                   max_concurrency=3, seed=i)
               for i, a in enumerate(["h2o-danube-3-4b", "hymba-1.5b"])]
        srv = MultiLLMServer(eps, BalanceAware(), batch_size=6)
        for i, p in enumerate(prompts):
            srv.submit(Request(rid=i, tokens=p, max_new=6))
        done = srv.run(null_route_features)
        assert len(done) == len(prompts)
        outs[name] = {r.rid: (r.endpoint, tuple(r.output)) for r in done}
        stats[name] = sum(e.batch_reprefills for e in eps)
    assert outs["paged"] == outs["restart"]
    assert stats["paged"] == 0
    assert stats["restart"] > 0        # the baseline restarts on every event


@pytest.mark.slow
def test_server_hedging_duplicates_and_cancels():
    """``hedge_after_steps``: a request still decoding that many chunks past
    admission is duplicated on the alternate endpoint; the first finisher
    wins, the sibling is cancelled and its slot/pages are released.  The pool
    decodes in lock-step, so the primary always wins here — outputs must be
    identical to the unhedged run, every rid completes exactly once, and
    both allocators drain back to full capacity."""
    from repro.configs import get_smoke_config
    from repro.core.baselines import BalanceAware
    from repro.serving.engine import (Endpoint, MultiLLMServer, Request,
                                      null_route_features)

    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 500, (9,)).astype(np.int32) for _ in range(3)]
    outs = {}
    for hedge in (0, 2):
        eps = [Endpoint(dataclasses.replace(get_smoke_config(a),
                                            dtype=jnp.float32),
                        max_concurrency=2, t_max=64, page_size=8,
                        sync_every=2, seed=i)
               for i, a in enumerate(["h2o-danube-3-4b", "hymba-1.5b"])]
        srv = MultiLLMServer(eps, BalanceAware(), batch_size=2,
                             hedge_after_steps=hedge)
        for i, p in enumerate(prompts):
            srv.submit(Request(rid=i, tokens=p, max_new=12))
        done = srv.run(null_route_features)
        rids = [r.rid for r in done]
        assert sorted(rids) == list(range(len(prompts)))   # once each
        outs[hedge] = {r.rid: tuple(r.output) for r in done}
        if hedge:
            assert srv.hedged > 0                  # the policy actually fired
            assert not srv._hedges and not srv._shadow_ids
        for ep in eps:                             # cancel freed everything
            assert len(ep.alloc.free_slots) == ep.L
            assert len(ep.alloc.free_pages) == ep.alloc.n_pages - 1
    assert outs[0] == outs[2]          # lock-step pool: primaries win
