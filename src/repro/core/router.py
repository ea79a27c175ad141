"""OmniRouter facade: two-stage routing (predict → constrained optimize).

``route`` consumes the array-based :class:`RouteBatch` contract.  When the
predictor implements the device predict contract (``token_len`` /
``device_inputs`` / ``predict_device`` — ECCOS-T, ECCOS-R and ECCOS-H all
do), the ONLY host work is tokenizing the query text: featurize → retrieve
→ vote → blend → solve → repair → polish trace into a single jit-compiled
function, so no intermediate (capability/cost matrices, neighbour indices)
ever round-trips to the host between the predictor and the solver.
Predictor state (encoder params, vector-store buffers, valid-row count) is
passed as arguments, so online store appends are picked up without
retracing (the store's capacity-doubling is the only recompile trigger).

Predictors without the device contract fall back to the two-call path
(``predict_arrays`` then ``DualSolver.route_arrays``).

Streaming (ISSUE 5): ``route_window`` makes the router stateful under the
hood — it threads a :class:`~repro.core.optimizer.DualState` through a
*streaming-tuned* solver (scale-free subgradient + stall early-exit) so
window k+1 warm-starts from window k's multipliers and the global budget/α
is enforced cumulatively over the stream.  The stateless ``route`` contract
is unchanged for offline callers, and the device path fuses
featurize→predict→window-solve into the same single jit boundary with the
stream state passed as arrays.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize as _sanitize
from repro.data.qaserve import QAServe
from repro.data import tokenizer
from .baselines import Policy, RouteBatch
from .optimizer import DualSolver, DualState, init_dual_state
from .speculative import AcceptanceTracker, expand_pair_columns, pair_index_arrays


@dataclasses.dataclass
class RouterConfig:
    alpha: float = 0.75          # quality constraint (paper default)
    budget: Optional[float] = None   # set -> budget-controllable mode
    iters: int = 150
    lr_quality: float = 4.0
    lr_budget: float = 50.0
    lr_workload: float = 0.5
    use_assign_kernel: bool = False  # fused Pallas path (1 launch per solve)
    # beyond-paper robustness: tighten the predicted-quality constraint by a
    # small margin during primal polish so prediction noise doesn't push the
    # realized SR below alpha (optimizing to the boundary of a *predicted*
    # constraint amplifies miscalibration)
    alpha_margin: float = 0.03
    # streaming solver (route_window only): scale-free subgradient makes one
    # O(1) lr meaningful in both modes; stall_tol banks the warm-start win
    # as an early exit.  The offline solver above is untouched.
    lr_stream: float = 3.0
    stall_tol: float = 0.01
    stall_patience: int = 3
    # query-axis shards for the streaming solver (ISSUE 6): >1 runs the
    # blocked dual solve on one device; under an active "query" mesh the
    # same blocks spread one-per-device via shard_map, bit-identical to the
    # single-device blocked solve.  1 adopts the mesh size automatically.
    shards: int = 1
    # failure plane (ISSUE 9): robust=True solves streaming windows against
    # the quality lower-confidence-bound q - kappa*sigma (Bernoulli sigma by
    # default) so predictor error can't overdraw the alpha ledger; kappa=0
    # is bit-identical to robust off.
    robust: bool = False
    kappa: float = 1.0
    # speculative cascade (ISSUE 10): (draft, verify) SpecPair columns grow
    # the streaming solve to (N, M + P) — pair p costs
    # c_draft + c_verify / E[accepted] and carries the verify model's
    # quality (greedy speculative decode is output-identical to the verify
    # model alone).  () is bit-neutral: the solve is exactly today's.
    spec_pairs: tuple = ()


class OmniRouter(Policy):
    """ECCOS with a pluggable predictor ('T' trained / 'R' retrieval /
    'H' hybrid)."""

    def __init__(self, predictor, cfg: RouterConfig = RouterConfig(),
                 name: str = "ECCOS"):
        self.predictor = predictor
        self.cfg = cfg
        self.name = name
        mode = "budget" if cfg.budget is not None else "quality"
        self.solver = DualSolver(
            mode=mode, iters=cfg.iters,
            lr_constraint=cfg.lr_budget if mode == "budget" else cfg.lr_quality,
            lr_workload=cfg.lr_workload, use_kernel=cfg.use_assign_kernel)
        # streaming windows run a scale-free, early-exiting variant; the
        # offline solver above keeps the paper's one-shot trajectory
        self.stream_solver = DualSolver(
            mode=mode, iters=cfg.iters, lr_constraint=cfg.lr_stream,
            lr_workload=cfg.lr_workload, use_kernel=cfg.use_assign_kernel,
            stall_tol=cfg.stall_tol, stall_patience=cfg.stall_patience,
            norm_grad=True, shards=cfg.shards,
            robust=cfg.robust, kappa=cfg.kappa)
        # speculative cascade: pair columns + the acceptance EWMAs that
        # reprice them (the engine records verify rounds into the tracker;
        # expected() re-enters the fused solve as a runtime array)
        self.pairs = tuple(cfg.spec_pairs)
        self.acceptance = (AcceptanceTracker(self.pairs) if self.pairs
                           else None)
        self.route_seconds = 0.0    # scheduling-overhead accounting (Fig. 3)
        self.predict_seconds = 0.0
        self._dual_iters = 0        # synced portion of the iteration count
        self._iters_pending: list = []  # device scalars awaiting one batch sync
        self.windows = 0            # streaming windows routed
        # jitted predict→solve programs, keyed by (kind, solver plan,
        # masked?): the solver dispatches blocked-vs-legacy and
        # mesh-vs-local at TRACE time, so a fused program built without a
        # mesh must not be reused after one is activated (and vice versa)
        self._fused: dict = {}

    def prepare(self, train_ds: QAServe):
        return self

    @property
    def dual_iters(self) -> int:
        """Total streaming dual iterations run.

        Per-window ``iters_run`` scalars stay on device and sync here, in
        one batched fetch, only when somebody actually reads the counter —
        never inside the routing hot loop.
        """
        if self._iters_pending:
            self._dual_iters += int(np.asarray(jnp.stack(self._iters_pending)).sum())
            self._iters_pending.clear()
        return self._dual_iters

    def observe(self, texts, correct, out_len):
        """Fold completed requests into the predictor's store (if it keeps
        one) — the scheduler / serving engine call this online.  Returns the
        absorbing predictor, or None when the predictor keeps no store (so
        fold accounting doesn't report folds that never happened)."""
        obs = getattr(self.predictor, "observe", None)
        return None if obs is None else obs(texts, correct, out_len)

    def _thresholds(self):
        """(solver threshold, polish threshold) — the polish value is only
        consulted in quality mode; budget mode polishes to the budget."""
        if self.cfg.budget is not None:
            return self.cfg.budget, self.cfg.budget
        return (self.cfg.alpha,
                min(self.cfg.alpha + self.cfg.alpha_margin, 1.0))

    # -- mesh-sharded prediction (ISSUE 6) -----------------------------------
    def _sharded_predict(self, plan):
        """The predict stage, spread over the query mesh when one is active:
        featurization, head inference and the retrieval vote are all
        per-query, so each device runs them on its local query shard with
        the predictor state (encoder params, VectorStore) REPLICATED — no
        collective is needed.  Without a mesh this is predict_device
        itself."""
        predictor = self.predictor
        mesh, axes, _ = plan

        def predict(inputs, tokens, input_len, price_in, price_out):
            cap, _, cost = predictor.predict_device(
                inputs, tokens, input_len, price_in, price_out)
            return cap, cost

        if mesh is None:
            return predict
        from jax.sharding import PartitionSpec as P
        qspec = P(axes if len(axes) > 1 else axes[0])
        rep = P()

        def sharded(inputs, tokens, input_len, price_in, price_out):
            in_specs = (jax.tree_util.tree_map(lambda _: rep, inputs),
                        qspec, qspec, rep, rep)
            return jax.shard_map(predict, mesh=mesh, in_specs=in_specs,
                                 out_specs=(qspec, qspec), check_vma=False)(
                inputs, tokens, input_len, price_in, price_out)

        return sharded

    def _fused_fn(self, kind: str, masked: bool = False):
        """Fetch (or build) the jitted predict→solve program for the
        CURRENT solver plan (mesh / shard count) and window masking."""
        solver = self.stream_solver if kind == "window" else self.solver
        plan = solver._plan()
        key = (kind, plan[0], plan[1], plan[2], masked)
        fn = self._fused.get(key)
        if fn is None:
            build = (self._build_fused_window if kind == "window"
                     else self._build_fused)
            fn = self._fused[key] = build(plan, masked)
        return fn

    def _build_fused(self, plan, masked: bool):
        solver = self.solver
        predict = self._sharded_predict(plan)

        def fused(inputs, tokens, input_len, price_in, price_out, avail,
                  threshold, polish_threshold):
            cap, cost = predict(inputs, tokens, input_len, price_in,
                                price_out)
            return solver.route_arrays(cost, cap, threshold, avail,
                                       polish_threshold=polish_threshold)

        return jax.jit(fused)

    def _build_fused_window(self, plan, masked: bool):
        solver = self.stream_solver
        margin = self.cfg.alpha_margin
        predict = self._sharded_predict(plan)
        pairs = self.pairs
        didx, vidx = pair_index_arrays(pairs)

        def fused(inputs, tokens, input_len, price_in, price_out, avail,
                  threshold, state, share, e_acc=None, n_valid=None):
            cap, cost = predict(inputs, tokens, input_len, price_in,
                                price_out)
            if pairs:
                # pair columns splice in between predict and solve, INSIDE
                # the jit boundary: the acceptance EWMA is a runtime array,
                # so repricing never retraces
                cost, cap = expand_pair_columns(cost, cap, didx, vidx, e_acc)
            return solver.route_window(cost, cap, threshold, avail, state,
                                       share=share, polish_margin=margin,
                                       n_valid=n_valid)

        # jit signatures are positional: fix one per (pairs?, masked?) so
        # optional args never shift position between calls
        if pairs and masked:
            return jax.jit(fused)
        if pairs:
            def paired(inputs, tokens, input_len, price_in, price_out, avail,
                       threshold, state, share, e_acc):
                return fused(inputs, tokens, input_len, price_in, price_out,
                             avail, threshold, state, share, e_acc)
            return jax.jit(paired)
        if masked:
            def masked_fn(inputs, tokens, input_len, price_in, price_out,
                          avail, threshold, state, share, n_valid):
                return fused(inputs, tokens, input_len, price_in, price_out,
                             avail, threshold, state, share, None, n_valid)
            return jax.jit(masked_fn)

        def unmasked(inputs, tokens, input_len, price_in, price_out, avail,
                     threshold, state, share):
            return fused(inputs, tokens, input_len, price_in, price_out,
                         avail, threshold, state, share)

        return jax.jit(unmasked)

    def route(self, batch: RouteBatch, rng=None) -> np.ndarray:
        if hasattr(self.predictor, "predict_device"):
            return self._route_device(batch)
        return self._route_hostpredict(batch)

    # StreamController opt-in: pad arrival windows to power-of-two buckets
    # (multiples of the shard count under a mesh) and pass n_valid, so the
    # fused window jit compiles O(log N) shapes and sharded windows divide
    # evenly across devices.
    pads_windows = True

    def window_multiple(self) -> int:
        """Bucket sizes must divide into this many query shards."""
        return self.stream_solver._plan()[2]

    def route_window(self, batch: RouteBatch, state: Optional[DualState],
                     *, share: float = 1.0, rng=None,
                     n_valid: Optional[int] = None):
        """Streaming window: predict → warm-started windowed solve, with
        the DualState threaded through the SAME single jit boundary as the
        one-shot path (state in, state out — no host round-trip between the
        predictor and the solver).  ``n_valid`` marks the valid-row prefix
        of a padded window (padding rows are masked out of the ledger).
        Returns ``(assignment, new_state)``."""
        if state is None:
            # pair columns extend the multiplier/ledger axis: the warm-start
            # state spans all M + P columns of the streaming solve
            state = init_dual_state(batch.m + len(self.pairs))
        state_in = state
        threshold = (self.cfg.budget if self.cfg.budget is not None
                     else self.cfg.alpha)
        e_acc = (jnp.asarray(self.acceptance.expected(), jnp.float32)
                 if self.pairs else None)
        if hasattr(self.predictor, "predict_device"):
            t0 = time.perf_counter()
            toks = jnp.asarray(tokenizer.encode_batch(
                batch.queries, self.predictor.token_len))
            t1 = time.perf_counter()
            self.predict_seconds += t1 - t0
            fn = self._fused_fn("window", masked=n_valid is not None)
            args = [self.predictor.device_inputs(), toks,
                    jnp.asarray(batch.input_len, jnp.float32),
                    jnp.asarray(batch.price_in, jnp.float32),
                    jnp.asarray(batch.price_out, jnp.float32),
                    jnp.asarray(batch.available, jnp.float32),
                    jnp.asarray(threshold, jnp.float32), state,
                    jnp.asarray(share, jnp.float32)]
            if self.pairs:
                args.append(e_acc)
            if n_valid is not None:
                args.append(jnp.asarray(n_valid, jnp.float32))
            x, info, state = fn(*args)
        else:
            t0 = time.perf_counter()
            cap, _, cost = self.predictor.predict_arrays(batch)
            t1 = time.perf_counter()
            self.predict_seconds += t1 - t0
            cost, cap = jnp.asarray(cost), jnp.asarray(cap)
            if self.pairs:
                didx, vidx = pair_index_arrays(self.pairs)
                cost, cap = expand_pair_columns(cost, cap, didx, vidx, e_acc)
            x, info, state = self.stream_solver.route_window(
                cost, cap, threshold,
                jnp.asarray(batch.available), state, share=share,
                polish_margin=self.cfg.alpha_margin, n_valid=n_valid)
        x = np.asarray(x)
        if _sanitize.active("ledgersan"):
            # the fused jit returns a concrete out-state; the monotone check
            # is the ledger coverage for this path (the solver-level
            # certificate hook only sees tracers inside the fusion)
            _sanitize.check_state_monotone(state_in, state,
                                           where="OmniRouter.route_window")
        # keep iters_run on device: int() here would add a second host sync
        # to every routing window (SC01); dual_iters sums lazily on read
        self._iters_pending.append(info.iters_run)
        self.windows += 1
        self.route_seconds += time.perf_counter() - t1
        return x, state

    def _route_device(self, batch: RouteBatch) -> np.ndarray:
        """Single-jit path: tokenize on host, everything else on device."""
        t0 = time.perf_counter()
        toks = jnp.asarray(tokenizer.encode_batch(
            batch.queries, self.predictor.token_len))
        t1 = time.perf_counter()
        self.predict_seconds += t1 - t0
        threshold, polish_threshold = self._thresholds()
        x, _ = self._fused_fn("route")(
            self.predictor.device_inputs(), toks,
            jnp.asarray(batch.input_len, jnp.float32),
            jnp.asarray(batch.price_in, jnp.float32),
            jnp.asarray(batch.price_out, jnp.float32),
            jnp.asarray(batch.available, jnp.float32),
            jnp.asarray(threshold, jnp.float32),
            jnp.asarray(polish_threshold, jnp.float32))
        x = np.asarray(x)
        self.route_seconds += time.perf_counter() - t1
        return x

    def _route_hostpredict(self, batch: RouteBatch) -> np.ndarray:
        """Legacy two-call path for predictors without the device contract."""
        t0 = time.perf_counter()
        cap, _, cost = self.predictor.predict_arrays(batch)
        t1 = time.perf_counter()
        self.predict_seconds += t1 - t0
        threshold, polish_threshold = self._thresholds()
        x, _ = self.solver.route_arrays(
            jnp.asarray(cost), jnp.asarray(cap), threshold,
            jnp.asarray(batch.available), polish_threshold=polish_threshold)
        x = np.asarray(x)
        self.route_seconds += time.perf_counter() - t1
        return x


def evaluate_assignment(ds: QAServe, x: np.ndarray) -> Dict[str, float]:
    """True SR and true $ cost of an assignment (uses ground truth)."""
    n = ds.n
    x = np.asarray(x)
    sr = float(ds.correct[np.arange(n), x].mean())
    cost = float(ds.cost_matrix()[np.arange(n), x].sum())
    return {"success_rate": sr, "cost": cost}
