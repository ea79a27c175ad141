"""Readings that set a cell's ``logit_gap`` limit, on the chip.

    python3 bench/control.py --workload <cell> --seeds 101,102,... \
        --seconds 15 --control 3

For each seed, in one process: set up the cell (weights from the seed),
serve its traffic open-loop for ``--seconds`` at the cell's own rate and
slots, free the program, and read the widest gap of the served tokens
against the fp32 reference (the sound program's reading).  For the first
``--control`` seeds, also read the control: at the same prompts and
tokens, the gap of the token the float8 reference puts first.  The lower
reading is the largest program reading; the upper one the smallest control
reading.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from bench import check
    spec = run.spec(args.workload)
    run.chip(spec["cell"]["chips"], rehearse=args.rehearse)
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = run.Cell(args.workload, seed, rehearse=args.rehearse)
        sched = cell.schedule(cell.params["rate_per_s"], args.seconds)
        cell.warm([sched])
        win, done, _ = cell.serve(sched, args.seconds)
        m = run.client_metrics(sched, win, done, args.seconds)
        served, msizes = cell.served(sched, done), cell.msizes
        del done
        cell.free()
        out = {"seed": seed, "served": len(served), "failed": m["failed"],
               **check.logit_gap(msizes, seed, served)}
        if k < args.control:
            out["control_gap"] = check.control_gap(msizes, seed,
                                                   served)["logit_gap"]
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
