"""Find a cell's knee: one set-up, then one window per offered rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 20 \
        --rates 2,4,6,8 [--trace-out DIR]

For each rate it prints the requests offered and failed, the tails, the
output tokens per second and the requests still unfinished when the window
closed (a backlog that grows with the run means the rate is past the knee).
The cell's fixed rate was set from such a sweep at about 0.8 of the knee.
``--trace-out`` traces the first window into DIR and prints what the trace
holds (planes, lines, the busiest module events and op names).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import run  # noqa: E402


def describe(trace_dir: str):
    """Print the structure of a trace, for writing its reduction."""
    from jax.profiler import ProfileData

    from bench import trace
    pd = ProfileData.from_file(trace.find_xplane(trace_dir))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name}: {len(lines)} lines")
        for line in lines[:12]:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            by = {}
            for e in evs:
                by.setdefault(e.name, [0, 0.0, None])
                by[e.name][0] += 1
                by[e.name][1] += e.duration_ns * 1e-6
                by[e.name][2] = by[e.name][2] or dict(e.stats)
            top = sorted(by.items(), key=lambda x: -x[1][1])
            top = top[:8] + [t for t in top[8:] if any(
                w in t[0].lower() for w in ("vote", "custom", "pallas"))]
            for name, (n, ms, st) in top:
                st = {k: v for k, v in (st or {}).items()
                      if k in ("hlo_module", "program_id", "run_id",
                               "long_name", "hlo_op", "tf_op")}
                print(f"    {name[:80]!r} x{n} {ms:.3f} ms {st}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--trace-out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    spec = run.spec(args.workload)
    run.chip(spec["cell"]["chips"], rehearse=args.rehearse)
    cell = run.Cell(args.workload, args.seed, rehearse=args.rehearse)
    scheds = [cell.schedule(r, args.seconds) for r in rates]
    cell.warm(scheds)
    print(f"sweep: set-up {run.time.perf_counter() - run.T_START:.1f}s",
          flush=True)
    for k, (rate, sched) in enumerate(zip(rates, scheds)):
        tdir = args.trace_out if (k == 0 and args.trace_out) else None
        win, done, counters = cell.serve(sched, args.seconds, trace_dir=tdir)
        m = run.client_metrics(sched, win, done, args.seconds)
        in_window = sum(1 for i, t in win.finish.items()
                        if t <= args.seconds)
        shares = np.bincount([r.endpoint for r in done if r.endpoint >= 0],
                             minlength=len(cell.cfgs))
        late = run.lateness(sched, win)
        print(json.dumps({"rate": rate, "offered": sched.n,
                          "finished_in_window": in_window,
                          "backlog_at_close": sched.n - in_window,
                          "shares": shares.tolist(), **m,
                          "route_calls": counters["route_calls"],
                          "route_ms": 1e3 * win.route_s / max(win.route_n, 1),
                          "lateness_p50_ms": 1e3 * float(np.median(late)),
                          "chunks_main": cell.endpoints[cell.main].busy_steps}),
              flush=True)
        if tdir:
            describe(tdir)
            from bench import trace
            evs = trace.load(trace.find_xplane(tdir))
            lo, _ = trace.window(evs)
            trace.save([e for e in evs if e.kind != "op"
                        or lo + 1.0 <= e.start < lo + 1.5],
                       os.path.join(tdir, "events.json"))
            os.remove(trace.find_xplane(tdir))
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"sweep: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


if __name__ == "__main__":
    main()
