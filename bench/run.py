"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's configuration file and mix, and
``bench/cells/<cell>.json``, ``bench/traffic/<mix>.json`` and
``bench/metrics/<metric>.py`` hold the rest.  Set-up builds the pool and the
router from the seed and warms every shape the run's schedule uses; the
window then offers the schedule open-loop on the wall clock for
``--seconds``, drains, and checks the served tokens against the plain
reference.  The last line of standard output is one JSON object.

``--rehearse`` serves the program's smoke config in place of the
full-width member, on the CPU, and prints counts and checks but no metric.
Without it the run refuses to start unless JAX finds a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

PAGE = 16            # Endpoint page size
DRAIN_S = 30.0       # a request due in the window and not finished this
#                      long after the window closed counts as failed
TRACE_AT = 0.4       # traced run: start at this share of the window ...
TRACE_FOR = 4.0      # ... and trace this many seconds


def spec(workload: str) -> dict:
    """The cell's entry, configuration, mix and cell parameters."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def load(rel):
        with open(os.path.join(ROOT, rel)) as f:
            return json.load(f)
    return dict(bench=bench, cell=cell, cfg=load(conf["file"]),
                mix=load(f"bench/traffic/{cell['traffic']}.json"),
                params=load(f"bench/cells/{workload}.json"))


def refuse(msg: str):
    print(f"bench: {msg}; nothing was run", file=sys.stderr)
    raise SystemExit(3)


class CompileWatch:
    """Counts compiles: backend compiles, and the compile requests that go
    through the persistent cache (a hit there compiles nothing on the
    backend, but still traces, lowers and loads a program)."""

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        self.requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1


class Cell:
    """Set-up of one cell for one seed: the pool, the router and their
    warm programs.  :meth:`serve` then runs one window."""

    def __init__(self, workload: str, seed: int, *, rehearse: bool):
        from bench import pool, traffic
        self.spec = spec(workload)
        s = self.spec
        self.cfg, self.mix, self.params = s["cfg"], s["mix"], s["params"]
        self.seed = seed
        self.cfgs = pool.model_configs(self.cfg, smoke_main=rehearse)
        self.main = self.cfg["main_column"]
        self.msizes = pool.main_sizes(self.cfg, self.cfgs[self.main])
        history = pool.qaserve(traffic.router_history(
            self.mix, pool=self.cfg["pool"], n=self.cfg["router_history"]),
            self.cfg["pool"])
        self.endpoints, self.router = pool.build(self.cfg, self.cfgs,
                                                 seed=seed, history=history)

    def schedule(self, rate: float, seconds: float):
        from bench import traffic
        return traffic.make_schedule(
            self.mix, pool=self.cfg["pool"], main_column=self.main, rate=rate,
            seconds=seconds, seed=self.seed,
            vocab=min(c.vocab_size for c in self.cfgs))

    def features(self, sched):
        from bench import pool
        return lambda rids: pool.qaserve(sched.pop.take(rids),
                                         self.cfg["pool"])

    def warm(self, scheds):
        """Compile every shape the schedules can reach: each endpoint's
        prefill (and page write) for every prompt bucket, its decode chunk,
        and each padded routing-window size up to the admission batch."""
        from bench import pool
        from repro.core.baselines import pad_bucket
        from repro.core.control import StreamController
        from repro.serving.engine import Request
        plens = sorted({len(p) - 1 for sc in scheds for p in sc.prompts})
        for ep in self.endpoints:
            for b in sorted({ep._bucket(p) for p in plens}):
                req = Request(rid=-1, tokens=np.ones(b + 1, np.int32),
                              max_new=1)
                ep.admit(req)
                ep.cancel(req)
            req = Request(rid=-1, tokens=np.ones(PAGE + 1, np.int32),
                          max_new=1)
            ep.admit(req)
            ep.step_end(ep.step_begin())
        server = pool.server(self.endpoints, self.router, horizon=1)
        ctrl = StreamController(self.router, horizon=scheds[0].n, stream=True)
        loads = np.array([float(e.L) for e in self.endpoints])
        counts = np.zeros_like(loads)
        feats = self.features(scheds[0])
        sizes, n = [], 1
        while True:
            sizes.append(n)
            if pad_bucket(n) >= server.batch_size:
                break
            n = pad_bucket(n) + 1
        for n in sizes + [1]:
            ctrl.route(feats(list(range(min(n, scheds[0].n)))), loads, counts)
            # the loop reads the lazily summed iteration count after every
            # window; its first reads compile small stacking programs
            ctrl.dual_iters

    def serve(self, sched, seconds: float, *, trace_dir=None):
        """Offer ``sched`` open-loop for ``seconds``, then drain.  Returns
        (window record, completed requests, server counters)."""
        from bench import clock, pool
        from repro.serving.engine import Request
        server = pool.server(self.endpoints, self.router, horizon=sched.n)
        win = clock.Window(seconds=seconds, drain_s=DRAIN_S, main=self.main)
        if trace_dir is not None:
            win.trace_at = TRACE_AT * seconds
            win.trace_for = min(TRACE_FOR, 0.5 * seconds)
            win.trace_dir = trace_dir
        clock.annotate(server, self.router, win)
        server._executor_cls = clock.executor_class(win)
        for i in range(sched.n):
            server.submit(Request(rid=i, tokens=sched.prompts[i],
                                  max_new=int(sched.max_new[i])),
                          at_step=float(sched.due[i]))
        feats = self.features(sched)
        # no collection pauses inside the window: what set-up left is frozen
        # out of the collector's reach, and it is switched off until the
        # drain ends
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            done = server.run(lambda batch: feats([r.rid for r in batch]),
                              max_steps=1 << 40)
        finally:
            gc.enable()
            gc.unfreeze()
        if win.tracing:
            import jax
            jax.profiler.stop_trace()
            win.tracing = False
            win.traced = (win.traced[0], win.now())
        clock.unwrap(self.endpoints, self.router)
        counters = {"route_calls": server.route_calls,
                    "dual_iters": server.dual_iters}
        return win, done, counters

    def numbers(self, win, served, failed: int):
        """Every number ``correct`` compares, and the logit comparison's
        record.  Needs no program state: run it after :meth:`free`."""
        from bench import check
        gap = check.logit_gap(self.msizes, self.seed, served)
        return ({"logit_gap": gap["logit_gap"], **check.windows(win.windows),
                 "failed": failed}, gap)

    def served(self, sched, done):
        """The full-width member's finished requests, for the reference."""
        from bench import check
        return [check.Served(np.asarray(r.tokens), np.asarray(r.output))
                for r in finished(sched, done).values()
                if r.endpoint == self.main]

    def free(self):
        """Drop the program's state so the reference has the chip."""
        self.endpoints = self.router = None
        gc.collect()


def finished(sched, done):
    """The requests served in full: as many tokens as the schedule asked."""
    return {r.rid: r for r in done
            if not r.failed and len(r.output or ()) == sched.max_new[r.rid]}


def client_metrics(sched, win, done, seconds: float) -> dict:
    """The end-to-end numbers, from the client's side: every request due in
    the window is in the sample; one not finished by the drain limit is
    failed and its time to first token counts as the wait until then."""
    fin = finished(sched, done)
    give_up = seconds + DRAIN_S
    ttft, tpot = [], []
    for i in range(sched.n):
        r = fin.get(i)
        if r is None:
            ttft.append(give_up - sched.due[i])
            continue
        ttft.append(win.first[i] - sched.due[i])
        if sched.max_new[i] > 1:
            tpot.append((win.finish[i] - win.first[i])
                        / (sched.max_new[i] - 1))
    return {
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "ttft_p95_ms": 1e3 * percentile(ttft, 95),
        "tpot_p95_ms": 1e3 * percentile(tpot, 95) if tpot else None,
        "output_tok_per_s": win.tokens_in_window / seconds,
        "failed": sched.n - len(fin),
    }


def member_times(sched, win, done, main: int) -> str:
    """TTFT p50/p95 and TPOT p95 (ms) of the requests the full-width member
    finished, beside the end-to-end ones over all requests."""
    fin = [r.rid for r in finished(sched, done).values()
           if r.endpoint == main]
    if not fin:
        return "none"
    ttft = [win.first[i] - sched.due[i] for i in fin]
    tpot = [(win.finish[i] - win.first[i]) / (sched.max_new[i] - 1)
            for i in fin if sched.max_new[i] > 1]
    return (f"TTFT p50 {1e3 * percentile(ttft, 50):.1f} p95 "
            f"{1e3 * percentile(ttft, 95):.1f}, TPOT p95 "
            f"{1e3 * percentile(tpot, 95) if tpot else 0.0:.2f}")


def lateness(sched, win) -> np.ndarray:
    """How late each request was released: the loop releases arrivals after
    each ``advance`` returns, so a request waits for the first return at or
    after its due time."""
    returns = np.asarray(win.returns)
    if not len(returns):
        return np.zeros(1)
    idx = np.minimum(np.searchsorted(returns, sched.due), len(returns) - 1)
    return np.maximum(returns[idx] - sched.due, 0.0)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile."""
    v = np.sort(np.asarray(values, float))
    return float(v[max(0, int(np.ceil(q / 100.0 * len(v))) - 1)])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, smoke-width member: counts and checks only")
    args = ap.parse_args(argv)
    cell_spec = spec(args.workload)
    devs = chip(cell_spec["cell"]["chips"], rehearse=args.rehearse)
    watch = CompileWatch()
    from bench import check

    cell = Cell(args.workload, args.seed, rehearse=args.rehearse)
    sched = cell.schedule(cell.params["rate_per_s"], args.seconds)
    cell.warm([sched])
    compiles0 = [ep.compile_count() for ep in cell.endpoints]
    watch0, requests0 = watch.n, watch.requests
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    setup_s = time.perf_counter() - T_START
    win, done, _ = cell.serve(sched, args.seconds, trace_dir=trace_dir)
    compiles_in_window = watch.n - watch0
    requests_in_window = watch.requests - requests0
    grew = [ep.compile_count() - c0
            for ep, c0 in zip(cell.endpoints, compiles0)]
    metrics = client_metrics(sched, win, done, args.seconds)
    metrics["setup_s"] = setup_s
    failed = metrics.pop("failed")
    shares = np.bincount([r.endpoint for r in done if r.endpoint >= 0],
                         minlength=len(cell.cfgs))
    member = member_times(sched, win, done, cell.main)
    late = lateness(sched, win)

    # --- memory, then free the program before the reference -------------
    stats = devs[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    served = cell.served(sched, done)
    trace_ctx = None
    if args.trace:
        from bench import trace
        trace_ctx = TraceContext(trace.load(trace.find_xplane(trace_dir)),
                                 win, cell.msizes, devs[0].device_kind,
                                 cell.endpoints[cell.main].sync_every)
        shutil.rmtree(trace_dir, ignore_errors=True)
    limits = cell.params["limits"]
    del done
    cell.free()
    t_ref = time.perf_counter()
    numbers, gap = cell.numbers(win, served, failed)
    ref_s = time.perf_counter() - t_ref
    correct = check.verdict(numbers, limits)

    print(f"bench: {sched.n} requests scheduled, {failed} failed; column "
          f"shares {shares.tolist()}; {len(served)} served by the full-width "
          f"member, {gap['tokens_compared']} tokens compared; the member's "
          f"own {member}", file=sys.stderr)
    print(f"bench: compiles inside the window: {compiles_in_window} (backend), "
          f"{requests_in_window} (requests through the persistent cache, "
          f"{requests0} in set-up); "
          f"compile_count growth per endpoint {grew}; set-up backend compile "
          f"{watch.secs:.1f}s", file=sys.stderr)
    print(f"bench: release lateness p50 {1e3 * np.median(late):.1f} ms, max "
          f"{1e3 * np.max(late):.1f} ms; idle sleep {win.sleep_s:.1f}s; "
          f"reference {ref_s:.1f}s", file=sys.stderr)
    gaps = np.diff(np.asarray([0.0] + win.returns))
    at = int(np.argmax(gaps))
    print(f"bench: longest loop iteration {1e3 * gaps[at]:.1f} ms, ending "
          f"{win.returns[at]:.2f} s into the window; longest route window "
          f"{1e3 * win.route_max:.1f} ms; longest admission "
          f"{1e3 * win.admit_max:.1f} ms", file=sys.stderr)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)

    result = {"correct": bool(correct), "attempted": int(sched.n),
              "failed": int(failed)}
    if args.rehearse:
        result["rehearsal"] = True
    else:
        dev = devs[0]
        result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                            "count": len(devs), "memory_peak_bytes": peak}
        bench = cell_spec["bench"]
        if args.trace:
            per_layer, breakdown = trace_ctx.reduce(bench, args.workload)
            result["metrics"] = per_layer
            result["device"]["busy_s"] = trace_ctx.busy_s
            result["device"]["window_s"] = trace_ctx.window_s
            result["breakdown"] = breakdown
        else:
            units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
            result["metrics"] = {k: {"value": v, "unit": units[k]}
                                 for k, v in metrics.items()
                                 if v is not None and k in units}
    result["checks"] = checks
    print(json.dumps(result))


def chip(chips: int, *, rehearse: bool):
    """The devices; refuses to run without a TPU (or with too few chips)
    unless rehearsing.  Places the persistent compile cache."""
    import jax
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            refuse(f"no TPU: JAX found {devs[0].platform!r}")
        if len(devs) < chips:
            refuse(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devs


class TraceContext:
    """What a per-layer reader gets: the reduced trace, the run's own
    records, and the full-width member's family (``family``, its ``sizes``
    as ``s``) for the work counts."""

    def __init__(self, events, win, msizes, kind, steps_per_chunk):
        from bench import families, peaks, trace
        self.events = events
        self.lo, self.hi = trace.window(self.events)
        self.window_s = self.hi - self.lo
        self.busy_s = float(np.mean([trace.device_busy(self.events, self.lo,
                                                       self.hi, d)
                                     for d in trace.devices(self.events)]
                                    or [0.0]))
        self.win = win
        self.family = families.load(msizes)
        self.s = self.family.sizes(msizes)
        self.peaks = peaks.peaks(kind)
        # no program of the full-width member can run a step faster than
        # its family's least bytes a step take at peak bandwidth
        step = self.family.step_floor_bytes(self) / self.peaks[
            "hbm_bytes_per_s"]
        self._floor = {"jit__chunk_fn": steps_per_chunk * step,
                       "jit_prefill": step}
        self._trace = trace

    def main_time(self, module: str):
        """(calls, device seconds) of the full-width member's programs of
        one module name."""
        progs = self._trace.programs(self.events, module)
        mine = self._trace.main_programs(progs, self._floor[module])
        return (sum(progs[p][0] for p in mine),
                sum(progs[p][1] for p in mine))

    def reduce(self, bench: dict, cell: str):
        metrics = {}
        for m in bench["per_layer"]:
            if cell not in m.get("workloads", [cell]):
                continue
            mod = importlib.import_module(f"bench.metrics.{m['name']}")
            v = mod.read(self)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {
            "device_ops": self._trace.top_modules(self.events),
            "idle_gaps": self._trace.idle_gaps(self.events, self.lo,
                                               self.hi)}
        return metrics, breakdown


if __name__ == "__main__":
    main()
