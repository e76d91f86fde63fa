#!/usr/bin/env python3
"""Readings that set the benchmark's limits and rates, for whoever changes the
benchmark.  Not run by ``run.py``.

    python3 bench/calibrate.py readings --workload W --seeds 1,2,... --seconds S
    python3 bench/calibrate.py sweep --workload W --rates 2,4,8 --seconds S

``readings`` serves the cell at its own load on each seed, in one process,
and prints per seed the numbers ``check.py`` compares together with each
control's on the same requests (the reference at a lower precision, see
its ``CONTROLS``), reading the gap of the token the control puts first at
each position, and whether ``check.passed`` takes the control's tokens in
place of the served ones.  The limit of ``max_logit_gap`` lies between the
largest sound reading and the smallest reading of the int4 control.

``sweep`` serves an open-loop cell at each offered rate and prints what it
sustained, to find the highest rate the system keeps up with.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time

from run import configure_cache, load_cell, require_chips, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    require_chips(spec["workload"]["chips"])
    configure_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.what == "readings":
        runs = [(seed, spec) for seed in seeds]
    else:
        runs = []
        for rate in (float(r) for r in args.rates.split(",")):
            s = copy.deepcopy(spec)
            s["mix"]["rate_per_s"] = rate
            runs.append((seeds[0], s))
    for seed, s in runs:
        t = time.perf_counter()
        r = run_cell(s, seed, args.seconds, False, t_start=t,
                     controls=args.what == "readings")
        row = {"seed": seed, "rate_per_s": s["mix"].get("rate_per_s"),
               "correct": r["correct"], "attempted": r["attempted"],
               **{k: v["value"] for k, v in r["metrics"].items()},
               **{k: v["value"] for k, v in r["checks"].items()},
               **{f"control_{name}_{k}": (c["correct"] if k == "correct"
                                          else c["checks"][k]["value"])
                  for name, c in r.get("controls", {}).items()
                  for k in ("max_logit_gap", "correct")},
               "info": r["info"], "memory_peak_bytes":
                   r["device"]["memory_peak_bytes"],
               "wall_s": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
