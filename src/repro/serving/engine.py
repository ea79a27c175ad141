"""Multi-LLM serving engine: the ECCOS router in front of a pool of zoo
models with paged-KV continuous batching, per-endpoint concurrency limits,
and straggler hedging.

Each :class:`Endpoint` owns one architecture and serves up to ``L``
concurrent sequences out of a **fixed-shape paged state**: KV lives in a
page pool ``(n_pages, page_size, K, D)`` shared by all slots, each slot owns
a row of a block table, and per-sequence lengths replace the packed batch's
single position.  Admitting a request prefills *only that request* (prompt
padded to a length bucket) and scatters its KV into free pages; a completion
frees pages without touching any other sequence.  Shapes never change, so an
endpoint compiles its decode loop exactly once and its prefill once per
prompt-length bucket — admissions and completions retrace nothing.

The decode inner loop is fused: ``sync_every`` single-token steps run as one
jitted ``lax.scan`` chunk with on-device argmax sampling and a done-mask, so
the host syncs once per chunk instead of once per token, and
:meth:`MultiLLMServer.run` dispatches every endpoint's chunk before blocking
on any result (async dispatch overlaps the pool).

:class:`RestartEndpoint` keeps the seed's restart-based batching (re-prefill
the whole packed, left-padded batch on every admit and completion) as the
benchmark baseline — ``benchmarks/bench_serving.py`` races the two.

The :class:`MultiLLMServer` runs on the SAME control loop as the
event-driven simulator (``repro.core.control.ControlLoop``): requests are
released by arrival step, admitted per the paper's capacity rule
(``AdmissionRule``), and routed through a Policy — with ``stream=True``,
through the persistent dual controller (``Policy.route_window``), whose
multipliers and budget/α ledger carry across windows while the live
per-endpoint in-flight counts feed the workload constraint.  True
cost/success is accounted via the QAServe ground truth when available.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize as _sanitize
from repro.common import tracing
from repro.common.guards import jit_cache_size as _jit_cache_size
from repro.configs.base import ModelConfig
from repro.core.control import (AdmissionRule, ControlLoop, FoldBuffer,
                                StreamController)
from repro.models import build_model
from repro.models.zoo import (PAGED_POOL_KEYS, pad_cache, pages_per_request,
                              prefill_into_pages, reset_slot)


def null_route_features(batch):
    """Feature producer for driving :class:`MultiLLMServer` without a
    dataset: a load-balancing-only RouteBatch (uniform prices/lengths, no
    ground truth).  Used by the serving benchmark and tests to isolate the
    serving plane from the prediction plane."""
    from repro.core.baselines import RouteBatch

    class _Features:
        queries = ["q"] * len(batch)

        def route_batch(self, loads, counts, with_truth=False):
            n, m = len(batch), len(loads)
            return RouteBatch(queries=["q"] * n, input_len=np.ones(n),
                              price_in=np.ones(m), price_out=np.ones(m),
                              loads=loads, counts=counts)

    return _Features()


@dataclasses.dataclass
class _SpecSeq:
    """One speculative sequence: a slot on BOTH pair endpoints, driven by
    the server's pair rounds instead of the chunk loop.  ``base`` is the
    accepted length (prompt + emitted tokens) — both endpoints' ``lens``
    mirrors equal it between rounds; ``pending`` is the next token to feed
    (the strong model's last emission, or the final prompt token)."""
    req: "Request"
    pair: int
    d_slot: int
    v_slot: int
    pending: int
    base: int
    remaining: int


@dataclasses.dataclass
class Request:
    """One request.  The stage stamps are ``time.perf_counter()`` seconds
    of the attempt that served it: released into the ready queue, admitted
    (prefill and page write dispatched), first token emitted, finished."""
    rid: int
    tokens: np.ndarray           # prompt token ids
    max_new: int = 16
    endpoint: int = -1
    output: Optional[List[int]] = None
    done: bool = False
    released: float = 0.0
    admitted: float = 0.0
    first_token: float = 0.0
    finished: float = 0.0
    hedged: bool = False
    admit_step: float = 0.0      # engine clock (decode chunk) at admission
    retries: int = 0             # failed attempts so far (failure plane)
    failed: bool = False         # permanently failed (retry budget spent)


class PageAllocator:
    """Host-side free lists for the paged state: physical KV pages and
    sequence slots.  Page 0 is the *dump page* — never handed out; free and
    finished slots keep their block-table rows zeroed so their (masked)
    in-flight writes land there instead of in anyone's live pages."""

    def __init__(self, n_pages: int, n_slots: int):
        self.n_pages = n_pages
        self.n_slots = n_slots
        self.free_pages: List[int] = list(range(n_pages - 1, 0, -1))
        self.free_slots: List[int] = list(range(n_slots - 1, -1, -1))
        # O(1) membership mirror of free_pages: the release-time double-free
        # assert was an O(n) list scan per page — quadratic at real pool sizes
        self._free_page_set = set(self.free_pages)
        # PageSan shadow allocator (repro.analysis.sanitize); None = off, and
        # the only cost on this path is the None check below
        self.san = None

    def alloc_pages(self, n: int) -> List[int]:
        if n > len(self.free_pages):
            raise RuntimeError(f"page pool exhausted: want {n}, "
                               f"free {len(self.free_pages)}")
        # take the tail in one slice + delete (same order as repeated pop())
        # so a failure above leaves the free list untouched — no partial
        # pops are ever observable
        pages = self.free_pages[:-n - 1:-1]
        del self.free_pages[len(self.free_pages) - n:]
        self._free_page_set.difference_update(pages)
        if self.san is not None:
            self.san.on_alloc_pages(pages)
        return pages

    def release_pages(self, pages: List[int]):
        for p in pages:
            assert 0 < p < self.n_pages and p not in self._free_page_set
            self.free_pages.append(p)
            self._free_page_set.add(p)
        if self.san is not None:
            self.san.on_release_pages(pages)

    def alloc_slot(self) -> int:
        if not self.free_slots:
            raise RuntimeError(f"slot pool exhausted: all {self.n_slots} "
                               f"slots in use")
        slot = self.free_slots.pop()
        if self.san is not None:
            self.san.on_alloc_slot(slot)
        return slot

    def release_slot(self, slot: int):
        assert slot not in self.free_slots
        self.free_slots.append(slot)
        if self.san is not None:
            self.san.on_release_slot(slot)


class Endpoint:
    """One pool member: a zoo model served from a fixed-shape paged state."""

    def __init__(self, cfg: ModelConfig, *, max_concurrency: int = 4,
                 t_max: int = 128, seed: int = 0, page_size: int = 16,
                 sync_every: int = 8):
        if cfg.family == "encdec":
            raise NotImplementedError("paged serving covers decoder LMs; "
                                      "serve enc-dec via RestartEndpoint")
        self.cfg = cfg
        self.L = max_concurrency
        self.page_size = page_size
        self.pages_per_slot = -(-t_max // page_size)
        self.t_max = self.pages_per_slot * page_size
        self.sync_every = sync_every
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(seed))

        probe = jax.eval_shape(
            lambda: self.model.empty_paged_state(1, 1, page_size))
        leaves_keys = {k for seg in probe["segs"] for layer in seg
                       for k in layer}
        self._has_kv = "k" in leaves_keys
        self._has_recurrent = bool(leaves_keys - set(PAGED_POOL_KEYS))
        # worst case: every slot at t_max, +1 for the dump page
        n_pages = 1 + self.L * self.pages_per_slot if self._has_kv else 1
        self.alloc = PageAllocator(n_pages, self.L)
        self._state = self.model.empty_paged_state(self.L, n_pages, page_size)

        # host mirrors of the per-slot device vectors
        self.block_table = np.zeros((self.L, self.pages_per_slot), np.int32)
        self.lens = np.zeros((self.L,), np.int32)
        self.remaining = np.zeros((self.L,), np.int32)
        self.last_tokens = np.zeros((self.L, 1), np.int32)
        self.slot_req: List[Optional[Request]] = [None] * self.L
        self._slot_pages: List[List[int]] = [[] for _ in range(self.L)]

        self._prefill = jax.jit(self.model.prefill)
        self._write = jax.jit(partial(prefill_into_pages,
                                      page_size=page_size),
                              donate_argnums=(0,))
        self._reset = jax.jit(reset_slot, donate_argnums=(0,))
        self._chunk = jax.jit(self._chunk_fn, donate_argnums=(1,))
        # speculative cascade plane: one verify jit (shape-cached per draft
        # window k) plus one k-step draft chunk per k — both created here /
        # at first pair attach, so compile_count stays constant under churn
        self._verify = jax.jit(self._verify_fn, donate_argnums=(1,))
        self._spec_chunks: dict = {}   # draft window k -> jitted k-step chunk
        self.spec_slots: set = set()   # slots driven by the speculative plane

        # the recorder's name for this endpoint: the index MultiLLMServer
        # gives it (-1: unlabelled)
        self.label = -1
        self.busy_steps = 0          # chunks dispatched
        self.decoded_tokens = 0      # real (non-masked) tokens emitted
        self.prefill_calls = 0       # one per admitted request
        self.batch_reprefills = 0    # ALWAYS 0 here — the restart metric

        if _sanitize.active("pagesan"):
            _sanitize.PageSan.attach(self)

    # -- instrumentation -----------------------------------------------------
    def _san_check(self):
        """Full PageSan audit between chunks; one None check when off."""
        san = self.alloc.san
        if san is not None:
            san.check_endpoint(self)

    def compile_count(self) -> int:
        """Total jit compilations across this endpoint's device functions.
        Constant once every prompt-length bucket has been seen — admissions
        and completions retrace nothing (the paged contract)."""
        return sum(_jit_cache_size(f) for f in
                   (self._prefill, self._write, self._reset, self._chunk,
                    self._verify, *self._spec_chunks.values()))

    def active_count(self) -> int:
        return self.L - len(self.alloc.free_slots)

    def has_capacity(self) -> bool:
        return bool(self.alloc.free_slots)

    def active_requests(self) -> List[Request]:
        return [r for r in self.slot_req if r is not None]

    def cancel(self, req: Request) -> bool:
        """Release a still-decoding request's slot and pages (hedging: the
        sibling copy finished first).  Must only run between chunks — the
        freed block-table row is zeroed so the slot's masked in-flight
        writes land on the dump page, and the slot immediately becomes
        admissible again."""
        for slot, r in enumerate(self.slot_req):
            if r is req:
                self.spec_slots.discard(slot)
                self.slot_req[slot] = None
                self.block_table[slot] = 0
                self.lens[slot] = 0
                self.remaining[slot] = 0
                self.last_tokens[slot, 0] = 0
                if self._has_kv:
                    self.alloc.release_pages(self._slot_pages[slot])
                    self._slot_pages[slot] = []
                self.alloc.release_slot(slot)
                self._san_check()
                return True
        return False

    def can_serve(self, req: Request) -> bool:
        """Whether the request fits this endpoint's fixed shapes at all:
        prompt + output budget within t_max.  Checked by the server at
        admission so an oversized request is failed, not crashed on."""
        return len(req.tokens) - 1 + req.max_new <= self.t_max

    # -- admission -----------------------------------------------------------
    def _bucket(self, plen: int) -> int:
        """Prompt-length bucket.  Attention KV tolerates right-pad garbage
        (masked by ``lens``), so pure-attention models bucket to page
        multiples — one prefill compilation per bucket.  Recurrent state
        (SSM/conv/xLSTM) integrates every input token, so hybrid models
        prefill at exact length to stay bit-identical."""
        if self._has_recurrent:
            return plen
        return -(-plen // self.page_size) * self.page_size

    def admit(self, req: Request):
        """Prefill this request only and wire its pages/slot into the fixed
        batch — no other sequence is touched, nothing is re-traced."""
        assert self.has_capacity()
        toks = np.asarray(req.tokens, np.int32)
        plen = len(toks) - 1            # last prompt token is fed to decode
        if plen + req.max_new > self.t_max:
            # before any slot/page mutation: the caller gets a clean error
            raise ValueError(f"request {req.rid} needs {plen + req.max_new} "
                             f"positions, endpoint t_max={self.t_max}")
        with tracing.span(f"admit.{self.label}", req.rid):
            req.output = []
            slot = self.alloc.alloc_slot()
            if self._has_kv:
                pages = self.alloc.alloc_pages(
                    pages_per_request(plen, req.max_new, self.page_size))
                self._slot_pages[slot] = pages
                self.block_table[slot] = 0
                self.block_table[slot, :len(pages)] = pages
            if plen > 0:
                bucket = self._bucket(plen)
                ptoks = np.zeros((1, bucket), np.int32)
                ptoks[0, :plen] = toks[:-1]
                with tracing.span(f"admit.prefill.{self.label}"):
                    cache, _ = self._prefill(self.params, jnp.asarray(ptoks))
                n_prefill_pages = (-(-bucket // self.page_size)
                                   if self._has_kv else 0)
                page_ids = np.asarray(
                    self._slot_pages[slot][:n_prefill_pages], np.int32)
                with tracing.span(f"admit.write.{self.label}"):
                    self._state = self._write(self._state, cache,
                                              jnp.asarray(page_ids),
                                              jnp.asarray(slot, jnp.int32))
                self.prefill_calls += 1
            elif self._has_recurrent:
                self._state = self._reset(self._state,
                                          jnp.asarray(slot, jnp.int32))
            self.lens[slot] = plen
            self.remaining[slot] = req.max_new
            self.last_tokens[slot, 0] = toks[-1]
            self.slot_req[slot] = req
            self._san_check()
        req.admitted = time.perf_counter()
        return slot

    # -- fused decode chunk --------------------------------------------------
    def _chunk_fn(self, params, state, block_table, last, lens, remaining,
                  length=None):
        """``length`` (default ``sync_every``) decode steps in one jit:
        on-device argmax sampling, done-mask freezes finished sequences
        (their writes land at their own frozen position, or the dump page
        once the slot is freed).  The host sees one sync per chunk."""
        length = self.sync_every if length is None else length

        def body(carry, _):
            state, last, lens, remaining = carry
            state, logits = self.model.decode_step_paged(
                params, state, last, block_table, lens)
            with jax.named_scope("sample"):
                nxt = jnp.argmax(logits[:, : self.cfg.vocab_size],
                                 axis=-1).astype(jnp.int32)
            active = remaining > 0
            nxt = jnp.where(active, nxt, 0)
            lens = lens + active.astype(jnp.int32)
            remaining = jnp.maximum(remaining - 1, 0)
            return (state, nxt[:, None], lens, remaining), nxt

        (state, last, lens, remaining), toks = jax.lax.scan(
            body, (state, last, lens, remaining), None, length=length)
        return state, last, lens, remaining, toks.T   # toks: (B, length)

    def step_begin(self):
        """Dispatch one decode chunk (async) — does not block."""
        if self.active_count() == 0:
            return None
        if self.spec_slots and all(
                req is None or slot in self.spec_slots
                for slot, req in enumerate(self.slot_req)):
            # every live slot is speculative: the pair rounds drive them,
            # so the frozen chunk would be pure wasted compute
            return None
        with tracing.span(f"chunk.dispatch.{self.label}"):
            out = self._chunk(self.params, self._state,
                              jnp.asarray(self.block_table),
                              jnp.asarray(self.last_tokens),
                              jnp.asarray(self.lens),
                              jnp.asarray(self.remaining))
        self._state = out[0]
        self.busy_steps += 1
        return out[1:]

    def step_end(self, pending) -> List[Request]:
        """Block on the chunk result, distribute tokens, free completions."""
        if pending is None:
            return []
        with tracing.span(f"chunk.fetch.{self.label}"):
            last, lens, remaining, toks = (np.array(x) for x in pending)
        with tracing.span(f"chunk.emit.{self.label}"):
            now = time.perf_counter()
            finished = []
            emitted = 0
            for slot, req in enumerate(self.slot_req):
                if req is None or slot in self.spec_slots:
                    # spec slots ride the chunk frozen (remaining 0); the
                    # server's pair rounds emit and complete them
                    continue
                take = int(min(self.remaining[slot], self.sync_every))
                if take and not req.output:
                    req.first_token = now
                req.output.extend(int(t) for t in toks[slot, :take])
                emitted += take
                if remaining[slot] == 0:
                    req.done = True
                    req.finished = now
                    finished.append(req)
                    self.slot_req[slot] = None
                    self.block_table[slot] = 0
                    if self._has_kv:
                        self.alloc.release_pages(self._slot_pages[slot])
                        self._slot_pages[slot] = []
                    self.alloc.release_slot(slot)
                    lens[slot] = 0
                    last[slot] = 0
            self.last_tokens = last
            self.lens = lens
            self.remaining = remaining
            self._san_check()
            self.decoded_tokens += emitted
            # the chunk's growth of the counts member_slot_use reads
            tracing.count("tokens", self.label, emitted)
            tracing.count("slot_steps", self.label, self.L * self.sync_every)
        return finished

    def step(self) -> List[Request]:
        """One decode chunk for every active sequence (dispatch + collect)."""
        return self.step_end(self.step_begin())

    # -- speculative cascade plane ---------------------------------------------
    # Spec slots hold a normal slot + pages but are frozen for the chunk
    # loop (remaining stays 0, step_end skips them); the server's pair
    # rounds drive them through draft_round / verify_round below and
    # advance ``lens`` only by the accepted length.  Every position >= lens
    # is written by a round before anything attends to it, so rejected
    # draft KV is never read — pages past the accepted prefix can therefore
    # be handed back to the allocator each round (rollback_pages) and
    # re-allocated fresh by the next round's ensure_pages.

    def can_serve_spec(self, req: Request, k: int) -> bool:
        """Spec variant of :meth:`can_serve`: the draft overshoots up to
        ``k - 1`` positions past the last accepted token, so the fixed
        shapes need that much headroom on top of prompt + output."""
        return len(req.tokens) - 1 + req.max_new + k - 1 <= self.t_max

    def admit_spec(self, req: Request, k: int) -> int:
        """Admit a speculative sequence: normal admission (prefill into
        pages), then freeze the slot and mark it spec-driven."""
        if self._has_recurrent or not self._has_kv:
            raise NotImplementedError(
                "speculative decode needs rollback-able paged KV "
                "(pure-attention models only)")
        if not self.can_serve_spec(req, k):
            raise ValueError(f"request {req.rid} + draft window {k} "
                             f"exceeds t_max={self.t_max}")
        slot = self.admit(req)
        self.remaining[slot] = 0
        self.spec_slots.add(slot)
        return slot

    def release_spec(self, slot: int):
        """Free a finished speculative slot through the normal paths."""
        self.spec_slots.discard(slot)
        self.slot_req[slot] = None
        self.block_table[slot] = 0
        self.lens[slot] = 0
        self.last_tokens[slot, 0] = 0
        self.alloc.release_pages(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self.alloc.release_slot(slot)
        self._san_check()

    def ensure_pages(self, slot: int, n_tokens: int):
        """Grow a spec slot's coverage to ``n_tokens`` positions before a
        round writes them (the inverse of :meth:`rollback_pages`)."""
        need = -(-n_tokens // self.page_size)
        have = len(self._slot_pages[slot])
        if need > have:
            pages = self.alloc.alloc_pages(need - have)
            self._slot_pages[slot].extend(pages)
            self.block_table[slot, have:need] = pages
            self._san_check()

    def rollback_pages(self, slot: int, n_tokens: int):
        """Release pages holding ONLY rejected draft positions (past the
        accepted prefix of ``n_tokens``) back through the allocator — the
        PageSan shadow sees real alloc/release churn every round."""
        keep = -(-n_tokens // self.page_size)
        pages = self._slot_pages[slot]
        if len(pages) > keep:
            self.alloc.release_pages(pages[keep:])
            self.block_table[slot, keep:len(pages)] = 0
            del pages[keep:]
            self._san_check()

    def _spec_chunk(self, k: int):
        fn = self._spec_chunks.get(k)
        if fn is None:
            fn = jax.jit(partial(self._chunk_fn, length=k),
                         donate_argnums=(1,))
            self._spec_chunks[k] = fn
        return fn

    def draft_round(self, slot_tokens: dict, k: int) -> np.ndarray:
        """Draft ``k`` tokens for every slot in ``slot_tokens`` (slot ->
        pending token) in one jitted k-step scan over the full fixed batch.
        Other slots ride along frozen (remaining 0): their in-flight writes
        land at their own frozen position, which the next chunk or round
        rewrites before anything attends to it.  Returns the (L, k) drafted
        token matrix; host mirrors are untouched — the draft's on-device
        lens advance is discarded, acceptance decides the real advance."""
        last = self.last_tokens.copy()
        rem = np.zeros_like(self.remaining)
        for slot, tok in slot_tokens.items():
            last[slot, 0] = tok
            rem[slot] = k
        out = self._spec_chunk(k)(
            self.params, self._state, jnp.asarray(self.block_table),
            jnp.asarray(last), jnp.asarray(self.lens), jnp.asarray(rem))
        self._state = out[0]
        self.busy_steps += 1
        tracing.count("slot_steps", self.label, self.L * k)
        return np.asarray(out[4])

    def _verify_fn(self, params, state, tokens, block_table, lens,
                   spec_mask, remaining):
        """One verify round in-jit: all k positions in ONE batched paged
        verify step, acceptance included.  Every decision (draft/strong
        matches, accepted prefix, emit count, next pending token) stays on
        device; the host syncs the three result arrays once per round."""
        state, logits = self.model.verify_step_paged(
            params, state, tokens, block_table, lens)
        strong = jnp.argmax(logits[:, :, : self.cfg.vocab_size],
                            axis=-1).astype(jnp.int32)          # (B, k)
        # tokens[:, 1:] are the draft continuations d_1..d_{k-1}; draft
        # position j survives iff it equals the strong argmax s_{j-1}
        matches = (tokens[:, 1:] == strong[:, :-1]).astype(jnp.int32)
        prefix = jnp.cumprod(matches, axis=1).sum(axis=1)       # (B,)
        # accepted prefix + the strong model's correction token, clamped by
        # the per-sequence output budget
        n_emit = jnp.minimum(prefix + 1, jnp.maximum(remaining, 1))
        n_emit = jnp.where(spec_mask, n_emit, 0).astype(jnp.int32)
        idx = jnp.maximum(n_emit - 1, 0)
        pending = jnp.take_along_axis(strong, idx[:, None], axis=1)[:, 0]
        return state, strong, n_emit, pending

    def verify_round(self, slot_tokens: dict, slot_rem: dict, k: int):
        """Verify every spec slot's k draft positions in one batched
        multi-position paged-decode step.  Non-spec rows are masked to the
        dump page (block table 0, len 0) so their k-position writes can
        never touch live pages.  Returns host (strong, n_emit, pending)
        from a single batched device transfer."""
        toks = np.zeros((self.L, k), np.int32)
        mask = np.zeros((self.L,), bool)
        rem = np.zeros((self.L,), np.int32)
        for slot, tv in slot_tokens.items():
            toks[slot] = tv
            mask[slot] = True
            rem[slot] = slot_rem[slot]
        bt = np.where(mask[:, None], self.block_table, 0)
        lens = np.where(mask, self.lens, 0)
        out = self._verify(self.params, self._state, jnp.asarray(toks),
                           jnp.asarray(bt), jnp.asarray(lens),
                           jnp.asarray(mask), jnp.asarray(rem))
        self._state = out[0]
        self.busy_steps += 1
        tracing.count("slot_steps", self.label, self.L * k)
        return jax.device_get(out[1:])


class RestartEndpoint:
    """The seed's restart-based batching, kept as the benchmark baseline:
    every admit and completion re-prefills the *entire* packed batch,
    left-pad realignment makes every sequence pay the longest sequence's
    cost, and the changing ``maxlen`` retraces prefill/decode per event."""

    def __init__(self, cfg: ModelConfig, *, max_concurrency: int = 4,
                 t_max: int = 128, seed: int = 0):
        self.cfg = cfg
        self.L = max_concurrency
        self.t_max = t_max
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self.active: List[Request] = []
        self._cache = None
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step)
        self.busy_steps = 0
        self.decoded_tokens = 0
        self.prefill_calls = 0
        self.batch_reprefills = 0

    def compile_count(self) -> int:
        return _jit_cache_size(self._prefill) + _jit_cache_size(self._decode)

    def active_count(self) -> int:
        return len(self.active)

    def has_capacity(self) -> bool:
        return len(self.active) < self.L

    def active_requests(self) -> List[Request]:
        return list(self.active)

    def cancel(self, req: Request) -> bool:
        """Drop a still-decoding request (hedging); restart-batching means
        the survivors pay one more re-prefill."""
        for k, r in enumerate(self.active):
            if r is req:
                self.active.pop(k)
                self._rebuild()
                return True
        return False

    def admit(self, req: Request):
        """Prefill the request and merge into the active batch by restarting
        (re-prefilling) the whole packed batch."""
        assert self.has_capacity()
        req.output = []
        self.active.append(req)
        self._rebuild()
        req.admitted = time.perf_counter()

    def _rebuild(self):
        if not self.active:
            self._cache = None
            return
        self.batch_reprefills += 1
        self.prefill_calls += 1
        maxlen = max(len(r.tokens) + len(r.output or []) for r in self.active)
        toks = np.zeros((len(self.active), maxlen), np.int32)
        for i, r in enumerate(self.active):
            seq = list(r.tokens) + list(r.output or [])
            toks[i, -len(seq):] = seq  # left-pad
        cache, _ = self._prefill(self.params, jnp.asarray(toks[:, :-1]))
        self._cache = pad_cache(cache, maxlen - 1 + self.t_max)
        self._last_tokens = jnp.asarray(toks[:, -1:])

    def step_begin(self):
        if not self.active:
            return None
        self._cache, logits = self._decode(self.params, self._cache,
                                           self._last_tokens)
        self.busy_steps += 1
        return logits

    def step_end(self, logits) -> List[Request]:
        if logits is None:
            return []
        nxt = np.asarray(jnp.argmax(
            logits[:, : self.cfg.vocab_size], axis=-1)).astype(np.int32)
        self._last_tokens = jnp.asarray(nxt[:, None])
        self.decoded_tokens += len(self.active)
        finished = []
        keep = []
        now = time.perf_counter()
        for i, r in enumerate(self.active):
            if not r.output:
                r.first_token = now
            r.output.append(int(nxt[i]))
            if len(r.output) >= r.max_new:
                r.done = True
                r.finished = now
                finished.append(r)
            else:
                keep.append(r)
        if finished:
            self.active = keep
            self._rebuild()
        return finished

    def step(self) -> List[Request]:
        """One batched decode step for every active sequence."""
        return self.step_end(self.step_begin())


class _EngineExecutor:
    """The endpoint pool behind the shared control loop
    (``repro.core.control.ControlLoop``): the stream clock is the decode
    step index, ``advance`` dispatches every endpoint's chunk before
    blocking on any result (jax async dispatch overlaps the pool), and the
    live per-endpoint in-flight counts are what the routing window sees."""

    def __init__(self, server: "MultiLLMServer", max_steps: int):
        self.server = server
        self.max_steps = max_steps
        self.steps = 0
        self.stopped = False
        self.requeue = None       # bound by ControlLoop: (req, at_step)
        self._progress: dict = {}  # id(req) -> (req, len(output), step) for
        #                            the stranded-request watchdog

    def now(self) -> float:
        return float(self.steps)

    def on_release(self, reqs):
        t = time.perf_counter()
        for req in reqs:
            req.released = t

    def loads(self) -> np.ndarray:
        srv = self.server
        vals = [float(e.L) for e in srv.endpoints]
        if srv.spec_pairs:
            pc = srv._pair_counts()
            for p, pair in enumerate(srv.spec_pairs):
                d_ep = srv.endpoints[pair.draft]
                v_ep = srv.endpoints[pair.verify]
                free = min(d_ep.L - d_ep.active_count(),
                           v_ep.L - v_ep.active_count())
                # a pair column can take min(free on both ends) MORE
                # sequences: report load so available == that headroom
                vals.append(float(pc[p] + free))
        return np.array(vals, float)

    def counts(self) -> np.ndarray:
        srv = self.server
        vals = [float(e.active_count()) for e in srv.endpoints]
        if srv.spec_pairs:
            vals.extend(float(c) for c in srv._pair_counts())
        return np.array(vals, float)

    def dispatch(self, items, x) -> List[Request]:
        rejected = []
        # one batch fetch; per-element int() on a device array would sync
        # the host once per request (SC01)
        x = np.asarray(x)
        srv = self.server
        plan = srv.fault_plan
        h = srv.health
        t = float(self.steps)
        for req, j in zip(items, x):
            j = int(j)
            if j >= len(srv.endpoints):
                # pair column: admit onto BOTH the pair's endpoints
                pair = srv.spec_pairs[j - len(srv.endpoints)]
                d_ep = srv.endpoints[pair.draft]
                v_ep = srv.endpoints[pair.verify]
                if not (d_ep.can_serve_spec(req, pair.k)
                        and v_ep.can_serve_spec(req, pair.k)):
                    req.done = True
                    req.endpoint = j
                    req.output = []
                    req.finished = time.perf_counter()
                    srv.completed.append(req)
                elif d_ep.has_capacity() and v_ep.has_capacity():
                    req.admit_step = float(self.steps)
                    srv.admit_spec(req, j - len(srv.endpoints))
                else:
                    rejected.append(req)
                continue
            ep = srv.endpoints[j]
            if not getattr(ep, "can_serve", lambda r: True)(req):
                # can NEVER fit this endpoint's fixed shapes: fail it cleanly
                # instead of crashing the server or re-queueing forever
                req.done = True
                req.endpoint = j
                req.output = []
                req.finished = time.perf_counter()
                srv.completed.append(req)
                continue
            if h is not None and not h.admissible(j):
                rejected.append(req)    # breaker open / probes exhausted
                continue
            if plan is not None:
                cap = plan.rate_limit(j, t)
                if cap is not None and ep.active_count() >= cap:
                    # 429: shed the request back to the queue, health hears
                    if h is not None:
                        h.record(j, False, None, now=t)
                    rejected.append(req)
                    continue
                if plan.down(j, t):
                    # connect-time failure on a dead endpoint
                    if h is not None:
                        h.record(j, False, None, now=t)
                    self._retry_or_fail(req)
                    continue
            if ep.has_capacity():
                req.endpoint = j
                req.admit_step = float(self.steps)
                ep.admit(req)
                if h is not None:
                    h.note_admit(j)
            else:  # paper's queueing: wait for capacity
                rejected.append(req)
        return rejected

    def advance(self, wake_at):
        if self.steps >= self.max_steps:
            self.stopped = True
            return [], False
        active = sum(e.active_count() for e in self.server.endpoints)
        if active == 0 and wake_at is not None and wake_at > self.steps:
            # pool idle, traffic still coming: jump to the next arrival
            self.steps = int(np.ceil(wake_at))
            return [], True
        # dispatch every endpoint's chunk before blocking on any result:
        # jax async dispatch overlaps the whole pool's decode work
        eps = self.server.endpoints
        plan = self.server.fault_plan
        pending = []
        for i in self._pool_order(len(eps)):
            if plan is not None and self._fault_skips(i):
                pending.append((i, eps[i], None))   # faulted: chunk skipped
            else:
                pending.append((i, eps[i], eps[i].step_begin()))
        done: List[Request] = []
        progressed = False
        for i, e, p in pending:
            fin = e.step_end(p)
            progressed = progressed or bool(fin) or bool(e.active_count())
            done.extend(fin)
        if self.server._spec:
            # pair rounds after the normal chunks: every round emits at
            # least the strong model's correction token, so this always
            # progresses
            done.extend(self.server._spec_round())
            progressed = True
        self.steps += 1
        done = self._resolve_hedges(self._completion_order(done))
        h = self.server.health
        events = []                 # (endpoint, ok, latency, rid)
        if h is not None:
            for req in done:
                events.append((int(req.endpoint), True,
                               float(self.steps) - float(req.admit_step),
                               int(req.rid)))
        if plan is not None:
            self._apply_flakes(plan, events)
        if self.server.stall_after_chunks > 0:
            self._watchdog(events)
        if h is not None:
            # canonical order: EWMA folds don't commute, and the racecheck
            # explorer permutes same-chunk completion order — sorting the
            # chunk's events makes the health state permutation-invariant
            for j, ok, lat, _ in sorted(events):
                h.record(j, ok, lat if ok else None, now=float(self.steps))
        for req in done:
            if req.first_token:
                tracing.record("request.queue", req.released, req.admitted,
                               req.rid)
                tracing.record("request.first", req.admitted,
                               req.first_token, req.rid)
        self.server.completed.extend(done)
        return done, progressed

    # -- ordering seams (identity here; the schedule race checker in
    # ``repro.analysis.sanitize.racecheck`` permutes them per seed to prove
    # same-chunk completions/hedges/cancels commute) --------------------------
    def _pool_order(self, k: int):
        return range(k)

    def _completion_order(self, done: List[Request]) -> List[Request]:
        return done

    def _fault_candidates(self):
        # ordering seam (see _pool_order): in-flight requests have no
        # inherent fault-sweep order within a chunk boundary — the race
        # checker permutes this to prove flake/watchdog failures commute
        return [(i, req) for i, ep in enumerate(self.server.endpoints)
                for req in ep.active_requests()]

    # -- fault injection (server.fault_plan; dormant when None) ----------------
    def _fault_skips(self, i: int) -> bool:
        """Whether endpoint ``i`` loses this decode chunk to a fault: a
        hard-down endpoint makes no progress at all; a latency spike of
        factor f advances one chunk in every f (so its effective service
        time stretches by f without touching the paged state)."""
        plan = self.server.fault_plan
        t = float(self.steps)
        if plan.down(i, t):
            return True
        f = plan.latency_factor(i, t)
        if f > 1.0 and self.steps % max(int(round(f)), 1) != 0:
            return True
        return False

    def _apply_flakes(self, plan, events):
        """Transient errors mid-decode: each active request flips a coin
        keyed on (endpoint, rid, step) — stateless, so the outcome is
        independent of sweep order and fresh every chunk."""
        t = float(self.steps)
        for i, req in self._fault_candidates():
            if req.rid in self.server._spec:
                continue    # spec sequences live outside the fault plane
            if plan.flake(i, t, req.rid, self.steps):
                if self.server.health is not None:
                    events.append((int(i), False, 0.0, int(req.rid)))
                self._fail_request(req)

    def _watchdog(self, events):
        """Stranded-request detector: a request whose output hasn't grown
        for ``stall_after_chunks`` chunks (its endpoint is dead or wedged)
        is cancelled via the normal ``Endpoint.cancel`` path — slot and
        pages drain to the free lists / dump page — and retried elsewhere."""
        k = self.server.stall_after_chunks
        cands = self._fault_candidates()
        seen = set()
        for i, req in cands:
            if req.rid in self.server._spec:
                continue    # spec sequences live outside the fault plane
            seen.add(id(req))
            out_len = len(req.output or ())
            ent = self._progress.get(id(req))
            if ent is None or ent[0] is not req or ent[1] != out_len:
                self._progress[id(req)] = (req, out_len, self.steps)
                continue
            if self.steps - ent[2] >= k:
                del self._progress[id(req)]
                seen.discard(id(req))
                if self.server.health is not None:
                    events.append((int(i), False, 0.0, int(req.rid)))
                self._fail_request(req)
        for key in [key for key in self._progress if key not in seen]:
            del self._progress[key]    # completed/failed: stop tracking

    def _fail_request(self, req: Request):
        """Remove a live request from the pool after a fault.  A hedged
        pair fails as a unit (both copies cancelled, the primary retries) —
        by this point in the chunk ``_resolve_hedges`` has already run, so
        a pair in ``_hedges`` has both copies still in flight."""
        srv = self.server
        pair = srv._hedges.pop(req.rid, None)
        if pair is not None:
            primary, pi, shadow, si = pair
            srv.endpoints[pi].cancel(primary)
            srv.endpoints[si].cancel(shadow)
            srv._shadow_ids.discard(id(shadow))
            self._retry_or_fail(primary)
            return
        if id(req) in srv._shadow_ids:
            srv._shadow_ids.discard(id(req))
            for ep in srv.endpoints:
                if ep.cancel(req):
                    break
            return                  # the primary carries the retry
        if not any(ep.cancel(req) for ep in srv.endpoints):
            return                  # already cancelled earlier this sweep
        self._retry_or_fail(req)

    def _retry_or_fail(self, req: Request):
        """Retry with exponential backoff while budget remains, else mark
        the request permanently failed (counts against the stream's SR)."""
        srv = self.server
        req.retries += 1
        req.endpoint = -1
        req.hedged = False
        req.done = False
        req.output = None
        if req.retries <= srv.retry_budget and self.requeue is not None:
            srv.retries += 1
            back = srv.backoff_steps * (2.0 ** (req.retries - 1))
            self.requeue(req, float(self.steps) + back)
        else:
            req.done = True
            req.failed = True
            req.output = []
            req.finished = time.perf_counter()
            srv.failures += 1
            srv.completed.append(req)

    def tick(self):
        """Post-event hook (same slot as the simulator's): fire the hedge
        policy.  Runs only between chunks — ``advance`` has synced every
        endpoint — so cancelling/duplicating slots is race-free."""
        self._maybe_hedge()

    # -- hedging (``_SimExecutor._maybe_hedge`` semantics, engine clock) -------
    def _pick_alt(self, primary: int, req: Request) -> Optional[int]:
        """Least-loaded endpoint other than the primary that has a free slot
        and fits the request's shapes."""
        best, best_free = None, 0
        h = self.server.health
        for j, ep in enumerate(self.server.endpoints):
            free = ep.L - ep.active_count()
            if (j != primary and free > best_free and ep.has_capacity()
                    and (h is None or h.admissible(j))
                    and getattr(ep, "can_serve", lambda r: True)(req)):
                best, best_free = j, free
        return best

    def _hedge_candidates(self):
        # ordering seam (see _pool_order): in-flight requests have no
        # inherent hedge-scan order within a chunk boundary
        return [(i, req) for i, ep in enumerate(self.server.endpoints)
                for req in ep.active_requests()]

    def _maybe_hedge(self):
        """Duplicate un-hedged slow decodes: a request still in flight
        ``hedge_after`` chunks past admission gets a sibling copy admitted
        on the least-loaded alternate endpoint.  First finisher wins; the
        straggler is cancelled at resolution (``_resolve_hedges``)."""
        srv = self.server
        if srv.hedge_after <= 0:
            return
        for i, req in self._hedge_candidates():
            if (req.hedged or req.done or req.rid in srv._spec
                    or self.steps - req.admit_step < srv.hedge_after):
                continue
            alt = self._pick_alt(i, req)
            if alt is None:
                continue
            shadow = dataclasses.replace(
                req, output=None, done=False, endpoint=alt, hedged=True,
                admit_step=float(self.steps))
            req.hedged = True
            srv._shadow_ids.add(id(shadow))
            srv._hedges[req.rid] = (req, i, shadow, alt)
            srv.endpoints[alt].admit(shadow)
            if srv.health is not None:
                srv.health.note_admit(alt)
            srv.hedged += 1

    def _resolve_hedges(self, done: List[Request]) -> List[Request]:
        """First finisher wins: report the PRIMARY request (with the
        winner's output/endpoint) exactly once and cancel the straggler
        sibling, freeing its slot immediately."""
        srv = self.server
        if not srv._hedges and not srv._shadow_ids:
            return done
        out: List[Request] = []
        for req in done:
            pair = srv._hedges.get(req.rid)
            if pair is None or (req is not pair[0] and req is not pair[2]):
                if id(req) in srv._shadow_ids:
                    srv._shadow_ids.discard(id(req))
                    continue            # sibling already resolved: drop copy
                out.append(req)
                continue
            primary, pi, shadow, si = pair
            del srv._hedges[req.rid]
            if req is shadow:
                srv._shadow_ids.discard(id(shadow))
                if primary.done:        # tie (same chunk): primary's own
                    continue            # completion stands, drop the copy
                srv.endpoints[pi].cancel(primary)
                primary.output = shadow.output
                primary.endpoint = shadow.endpoint
                primary.done = True
                primary.admitted = shadow.admitted
                primary.first_token = shadow.first_token
                primary.finished = shadow.finished
                out.append(primary)
            else:                       # primary won: kill the shadow
                if not shadow.done:
                    srv.endpoints[si].cancel(shadow)
                    srv._shadow_ids.discard(id(shadow))
                out.append(req)
        return out


class MultiLLMServer:
    """Router + endpoint pool behind the shared streaming control loop:
    admission per the paper's capacity rule, arrival-step release, optional
    persistent dual controller (``stream=True`` threads a DualState through
    ``policy.route_window`` so multipliers and the budget/α ledger carry
    across windows), and online fold-back of completed requests into the
    router's vector store."""

    # executor factory, overridable per-instance: the schedule race checker
    # swaps in a seeded event-order-permuting subclass
    _executor_cls = _EngineExecutor

    def __init__(self, endpoints: List[Endpoint], policy, *,
                 batch_size: int = 0, hedge_after_steps: int = 0,
                 fold_online: bool = False, fold_chunk: int = 0,
                 stream: bool = False, horizon: int = 0,
                 window_steps: float = 0.0, fault_plan=None, health=None,
                 retry_budget: int = 2, backoff_steps: float = 4.0,
                 stall_after_chunks: int = 0, spec_pairs=(),
                 adapt_window=None):
        self.endpoints = endpoints
        for j, ep in enumerate(endpoints):
            ep.label = j            # the recorder's name for the endpoint
        self.policy = policy
        cap = sum(e.L for e in endpoints)
        self.rule = AdmissionRule(batch_size).resolve(cap)
        self.batch_size = self.rule.batch_size
        self.max_inflight = self.rule.max_inflight
        self.hedge_after = hedge_after_steps
        self.fold_online = fold_online
        self.fold_chunk = fold_chunk or self.batch_size
        self.stream = stream
        self.horizon = horizon
        self.window_steps = window_steps
        # --- failure plane (ISSUE 9); every hot-path consult is gated on
        # `is not None` / `> 0`, so the off state costs one check ---
        self.fault_plan = fault_plan         # serving.faults.FaultPlan
        if health is True:
            from repro.core.health import HealthTracker
            health = HealthTracker(len(endpoints))
        self.health = health                 # core.health.HealthTracker
        self.retry_budget = retry_budget
        self.backoff_steps = backoff_steps   # retry k re-enters after 2^k*this
        self.stall_after_chunks = stall_after_chunks  # watchdog: no output
        #                                      growth for K chunks -> cancel
        self.adapt_window = adapt_window     # core.control.AdaptiveWindow
        # --- speculative cascade plane (ISSUE 10): router-selected
        # (draft, verify) pair columns; must MATCH the policy's
        # RouterConfig.spec_pairs when the policy is an OmniRouter ---
        self.spec_pairs = tuple(spec_pairs)
        self._spec: dict = {}       # rid -> _SpecSeq
        self.spec_rounds = 0        # per-sequence verify rounds run
        self.spec_emitted = 0       # tokens emitted by the spec plane
        if self.spec_pairs:
            if self.health is not None:
                raise NotImplementedError(
                    "speculative pair columns extend loads/counts past the "
                    "HealthTracker's model axis; run spec pools without "
                    "health (acceptance EWMAs do the pair repricing)")
            for p in self.spec_pairs:
                for j in (p.draft, p.verify):
                    ep = self.endpoints[j]
                    if getattr(ep, "_has_recurrent", True) \
                            or not getattr(ep, "_has_kv", False):
                        raise NotImplementedError(
                            f"pair endpoint {j} ({ep.cfg.name}) is not a "
                            f"pure-attention paged endpoint; speculative "
                            f"decode needs rollback-able paged KV")
        self.failures = 0                    # requests failed past the budget
        self.retries = 0                     # attempts re-entered the queue
        self.queue: deque = deque()     # (arrival_step, Request)
        self.completed: List[Request] = []
        self._fold_buf: List[Request] = []   # direct fold-back entry point
        self.folded = 0
        self.route_calls = 0
        self.route_seconds = 0.0             # in the recorder's route spans
        self.windows = 0
        self.dual_iters = 0
        self.hedged = 0                      # hedge duplicates fired
        self._hedges: dict = {}              # rid -> (primary, i, shadow, j)
        self._shadow_ids: set = set()        # id() of live shadow copies
        self._controller: Optional[StreamController] = None

    def submit(self, req: Request, at_step: float = 0.0):
        """Queue a request; ``at_step`` releases it into the stream once
        the engine clock (decode step index) reaches it.

        A request NO endpoint can fit is failed here, before it is ever
        routed — otherwise the streaming ledger would charge its predicted
        cost/quality for work that is never served and the budget would
        drift (the dual controller's accounting records what was routed)."""
        if self.endpoints and not any(
                getattr(ep, "can_serve", lambda r: True)(req)
                for ep in self.endpoints):
            req.done = True
            req.output = []
            req.finished = time.perf_counter()
            self.completed.append(req)
            return
        self.queue.append((float(at_step), req))

    def _inflight(self) -> int:
        return sum(e.active_count() for e in self.endpoints)

    # -- speculative cascade plane ---------------------------------------------
    def _pair_counts(self) -> List[int]:
        counts = [0] * len(self.spec_pairs)
        for s in self._spec.values():
            counts[s.pair] += 1
        return counts

    def admit_spec(self, req: Request, pair_idx: int):
        """Admit one request speculatively: a slot + prompt prefill on BOTH
        the pair's endpoints, driven by :meth:`_spec_round` from then on."""
        pair = self.spec_pairs[pair_idx]
        d_slot = self.endpoints[pair.draft].admit_spec(req, pair.k)
        v_slot = self.endpoints[pair.verify].admit_spec(req, pair.k)
        req.endpoint = len(self.endpoints) + pair_idx
        plen = len(req.tokens) - 1
        self._spec[req.rid] = _SpecSeq(
            req=req, pair=pair_idx, d_slot=d_slot, v_slot=v_slot,
            pending=int(req.tokens[-1]), base=plen, remaining=req.max_new)

    def _spec_round(self) -> List[Request]:
        """One draft+verify round for every live speculative sequence,
        batched per pair: the draft endpoint decodes k tokens in one k-step
        chunk, the verify endpoint scores all k positions in ONE batched
        multi-position paged step, and the longest strong-matching prefix
        plus the strong correction token is emitted.  Emissions are always
        strong-model argmaxes, so spec output is bit-identical to decoding
        on the verify endpoint alone.  Rejected draft pages roll back
        through the allocator; live acceptance feeds the router's pair-cost
        EWMAs (AcceptanceTracker — the HealthTracker-style repricing)."""
        with tracing.span("spec.round"):
            finished: List[Request] = []
            acc = getattr(self.policy, "acceptance", None)
            for p, pair in enumerate(self.spec_pairs):
                seqs = [s for s in self._spec.values() if s.pair == p]
                if not seqs:
                    continue
                d_ep = self.endpoints[pair.draft]
                v_ep = self.endpoints[pair.verify]
                k = pair.k
                for s in seqs:
                    d_ep.ensure_pages(s.d_slot, s.base + k)
                    v_ep.ensure_pages(s.v_slot, s.base + k)
                draft = d_ep.draft_round({s.d_slot: s.pending for s in seqs}, k)
                v_tokens, v_rem = {}, {}
                for s in seqs:
                    row = np.empty((k,), np.int32)
                    row[0] = s.pending
                    row[1:] = draft[s.d_slot, : k - 1]
                    v_tokens[s.v_slot] = row
                    v_rem[s.v_slot] = s.remaining
                strong, n_emit, pending = v_ep.verify_round(v_tokens, v_rem, k)
                now = time.perf_counter()
                emitted = 0
                for s in seqs:
                    ne = int(n_emit[s.v_slot])
                    if ne and not s.req.output:
                        s.req.first_token = now
                    s.req.output.extend(int(t) for t in strong[s.v_slot, :ne])
                    emitted += ne
                    s.base += ne
                    s.remaining -= ne
                    s.pending = int(pending[s.v_slot])
                    d_ep.lens[s.d_slot] = s.base
                    v_ep.lens[s.v_slot] = s.base
                    d_ep.last_tokens[s.d_slot, 0] = s.pending
                    v_ep.last_tokens[s.v_slot, 0] = s.pending
                    d_ep.rollback_pages(s.d_slot, s.base)
                    v_ep.rollback_pages(s.v_slot, s.base)
                    if acc is not None:
                        acc.record(p, ne)
                    self.spec_rounds += 1
                    self.spec_emitted += ne
                    if s.remaining <= 0:
                        req = s.req
                        req.done = True
                        req.finished = now
                        d_ep.release_spec(s.d_slot)
                        v_ep.release_spec(s.v_slot)
                        del self._spec[req.rid]
                        finished.append(req)
                v_ep.decoded_tokens += emitted
                tracing.count("tokens", v_ep.label, emitted)
        return finished

    def _fold(self, route_features, *, force: bool = False):
        """Fold ``_fold_buf`` into the policy's store — the manual entry
        point for completions that did not flow through :meth:`run` (the
        loop folds its own through a :class:`FoldBuffer`)."""
        if not self.fold_online or not self._fold_buf:
            return
        if not force and len(self._fold_buf) < self.fold_chunk:
            return
        from repro.core.scheduler import fold_completions
        if fold_completions(self.policy, route_features(self._fold_buf),
                            np.arange(len(self._fold_buf))):
            self.folded += len(self._fold_buf)
        self._fold_buf.clear()

    def run(self, route_features, *, max_steps: int = 10_000):
        # ONE controller for the server's lifetime: the DualState ledger
        # and warm multipliers must survive across run() calls (an early
        # max_steps exit requeues work for the next call — re-solving it
        # against a reset budget would double-spend)
        if self._controller is None:
            self._controller = StreamController(
                self.policy, horizon=self.horizon or len(self.queue),
                stream=self.stream, health=self.health,
                adapt_window=self.adapt_window)
        controller = self._controller
        windows0 = controller.windows
        iters0 = controller.dual_iters
        fold = FoldBuffer(self.policy, route_features,
                          enabled=self.fold_online, chunk=self.fold_chunk)
        items = [req for _, req in self.queue]
        times = np.array([t for t, _ in self.queue])
        self.queue.clear()
        executor = self._executor_cls(self, max_steps)
        route0 = tracing.total("route")
        loop = ControlLoop(
            executor=executor, controller=controller, rule=self.rule,
            items=items, features=route_features, fold=fold,
            arrival_times=times, window=self.window_steps,
            drain_admissions=False, requeue_front=True, health=self.health)
        loop.run()
        # an early exit (max_steps) leaves un-served requests in the loop's
        # queues — put them back, REBASED to the fresh clock a later run()
        # starts with (already-released items are due immediately), so the
        # next call picks them up instead of silently dropping them
        now = executor.now()
        for req in loop.ready:
            self.queue.append((0.0, req))
        for at, _, req in loop.pending:
            self.queue.append((max(0.0, at - now), req))
        self.route_seconds += tracing.total("route") - route0
        self.route_calls += controller.windows - windows0
        self.folded += fold.folded
        self.windows += controller.windows - windows0
        self.dual_iters += controller.dual_iters - iters0
        return self.completed
