"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. ``--fast`` shrinks rounds/seeds;
the full run reproduces the qualitative claims of Section 6. ``--json``
additionally writes the rows to ``BENCH_<platform>.json`` in the repo root so
the perf trajectory is tracked across PRs (CI uploads it as an artifact).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

# allow both `python -m benchmarks.run` and `python benchmarks/run.py`
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

MODULES = [
    "benchmarks.bench_history",        # Table 1
    "benchmarks.bench_mlmc",           # Lemma 3.1
    "benchmarks.bench_aggregators",    # kernels micro
    "benchmarks.bench_scan_driver",    # compiled vs Python-loop driver
    "benchmarks.bench_model_zoo",      # unified zoo driver + memory gate
    "benchmarks.bench_momentum_fails",  # Fig 3/4 (App. E)
    "benchmarks.bench_periodic",       # Fig 1/5
    "benchmarks.bench_bernoulli",      # Fig 2/8
    "benchmarks.bench_failsafe",       # Eq. 6 / Thm 4.1 ablation
    "benchmarks.bench_serve",          # aggregation service throughput
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None, help="substring filter on module")
    ap.add_argument("--json", action="store_true",
                    help="also write rows to BENCH_<platform>.json")
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    all_rows = []
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            rows = mod.main(fast=args.fast)
            for r in rows:
                print(r, flush=True)
            all_rows.extend(rows)
            print(f"{mod_name},{(time.time()-t0)*1e6:.0f},module_wall_s="
                  f"{time.time()-t0:.1f}", flush=True)
        except Exception as e:  # keep the suite going, report at the end
            failures += 1
            print(f"{mod_name},,ERROR={type(e).__name__}:{e}", file=sys.stderr)
    if args.json:
        import jax
        path = os.path.join(os.path.dirname(__file__), "..",
                            f"BENCH_{jax.default_backend()}.json")
        recs = []
        for r in all_rows:
            name, us, derived = (r.split(",", 2) + ["", ""])[:3]
            recs.append({"name": name, "us_per_call": float(us) if us else None,
                         "derived": derived})
        with open(path, "w") as f:
            json.dump({"fast": args.fast, "rows": recs}, f, indent=1)
        print(f"# wrote {os.path.abspath(path)}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
