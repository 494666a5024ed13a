#!/usr/bin/env python3
"""Read the numbers that decide a cell's ``correct`` over many seeds: for
the program, for the control and for each fault the cell can have. The
limits in ``checks/<cell>.json`` are set from these readings (PERF.md).

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1001-1012 --control 3 --faults 3

One process: the cell's programs compile once, then per seed the program
runs its checked steps (or its grid) and the reference follows; on the
first ``--control`` seeds the control (the reference one precision step
down) and on the first ``--faults`` seeds each planted fault is compared
with the reference the same way (for a cell of several workers, one fault
leaves their exchange out). Prints one JSON line per reading, with each
device's bytes in use at that reference run's high point, and a summary:
per number the largest program reading (the lower reading) and the
smallest control and fault readings (candidates for the upper one).
"""
from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import run as bench_run  # noqa: E402


def seed_list(text: str):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b or a) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1001-1012,2001")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = seed_list(args.seeds)
    bench = bench_run.read_json(os.path.join(bench_run.ROOT,
                                             "BENCHMARK.json"))
    run = bench_run.Run.find(bench, args.workload, seeds[0], 0.0, False)
    run.limits = {k: math.inf for k in run.limits}
    devices = bench_run.start(run)
    driver = bench_run.make_driver(run, devices)
    t0 = time.perf_counter()
    compare = importlib.import_module(type(driver).__module__).compare
    driver.build()
    print(f"build {time.perf_counter() - t0:.1f} s", file=sys.stderr,
          flush=True)
    found = {}

    def note(kind: str, seed: int, checks, seconds: float, ref: dict):
        line = {"kind": kind, "seed": seed, "seconds": round(seconds, 2),
                **{n: v for n, v, _ in checks}}
        if "memory" in ref:  # the reference run's bytes per device
            line["memory"] = ref["memory"]
        print(json.dumps(line), flush=True)
        for n, v, _ in checks:
            found.setdefault(kind, {}).setdefault(n, []).append(v)

    for i, seed in enumerate(seeds):
        run.seed = seed
        t = time.perf_counter()
        driver.prepare()
        prog = driver.program_readings()
        driver.release()
        ref = driver.reference()
        note("program", seed, compare(prog, ref, run.limits),
             time.perf_counter() - t, ref)
        if i < args.control:
            t = time.perf_counter()
            ctl = driver.reference(**driver.CONTROL)
            note("control", seed, compare(ctl, ref, run.limits),
                 time.perf_counter() - t, ctl)
        if i < args.faults:
            for name, kw in driver.FAULTS.items():
                t = time.perf_counter()
                f = driver.reference(**kw)
                note(f"fault:{name}", seed, compare(f, ref, run.limits),
                     time.perf_counter() - t, f)
    summary = {kind: {n: (max(v) if kind == "program" else min(v))
                      for n, v in nums.items()}
               for kind, nums in found.items()}
    print(json.dumps({"summary": summary, "device": devices[0].device_kind,
                      "seeds": seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
