"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Scale presets (see common.SCALES):
  tiny  (default) laptop-class, minutes
  quick           small-server, tens of minutes
  full            the paper's c=20,000 / 3-year / SLA 1e-4 setting

Usage: PYTHONPATH=src python -m benchmarks.run [--scale tiny] [--only table2]
                                               [--json BENCH_tiny.json]

``--json`` additionally records the rows (plus scale/seed metadata) to a
JSON file, so speedups land in a committable BENCH_<scale>.json artifact.
When the file already exists *for the same scale*, rows are merged by name
(matching rows replaced, new rows appended, everything else kept) — a
``--only`` subset run refreshes just its own rows instead of clobbering the
artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.compile_cache import enable_compile_cache

from . import (ablation_marginal, drift_bench, fig1_priors, fig2_pricing,
               fleet_bench, kernels_bench, roofline, scenarios, serve_bench,
               table2_policies, tuning_bench)

MODULES = {
    "kernels": kernels_bench,
    "roofline": roofline,
    "table2": table2_policies,
    "fig1": fig1_priors,
    "fig2": fig2_pricing,
    "ablation_marginal": ablation_marginal,
    "scenarios": scenarios,
    "fleet": fleet_bench,
    "tuning": tuning_bench,
    "serve": serve_bench,
    "drift": drift_bench,
}


def merge_records(path: str, scale: str, seed: int, total: float,
                  records: list):
    """Merge fresh rows into an existing artifact by name (same scale only —
    a different scale's artifact is simply replaced).

    Provenance stays honest across subset merges: rows carried over keep
    their own recorded ``seed``, the artifact-level ``seed`` degrades to
    ``"mixed"`` when runs disagree, and ``total_seconds`` accumulates the
    compute recorded in the artifact rather than pretending the last subset
    run measured everything."""
    try:
        with open(path, encoding="utf-8") as f:
            old = json.load(f)
    except (OSError, ValueError):
        return seed, round(total, 1), records
    if old.get("scale") != scale:
        return seed, round(total, 1), records
    fresh = {r["name"]: r for r in records}
    carried = sum(1 for r in old.get("rows", []) if r["name"] not in fresh)
    merged = [fresh.pop(r["name"], r) for r in old.get("rows", [])]
    merged += list(fresh.values())
    if carried == 0:
        # nothing survived from the old artifact: this run's provenance IS
        # the artifact's provenance
        return seed, round(total, 1), merged
    # rows vote with their own seed; legacy rows (no per-row field) carry
    # the old artifact header's seed
    seeds = {r.get("seed", old.get("seed")) for r in merged}
    seeds.discard(None)
    merged_seed = seeds.pop() if len(seeds) == 1 else "mixed"
    merged_total = round(float(old.get("total_seconds", 0.0)) + total, 1)
    return merged_seed, merged_total, merged


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="tiny", choices=["tiny", "quick", "full"])
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset: " + ",".join(MODULES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to a BENCH_<scale>.json artifact")
    args = ap.parse_args()
    enable_compile_cache()

    names = list(MODULES) if not args.only else args.only.split(",")
    print("name,us_per_call,derived")
    t0 = time.time()
    records = []
    for name in names:
        mod = MODULES[name]
        try:
            for row in mod.run(args.scale, args.seed):
                print(row, flush=True)
                bench, us, derived = row.split(",", 2)
                records.append({"name": bench, "us_per_call": float(us),
                                "derived": derived, "seed": args.seed})
        except Exception as e:  # pragma: no cover
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            raise
    total = time.time() - t0
    if args.json:
        seed, total_s, rows = args.seed, round(total, 1), records
        if os.path.exists(args.json):
            seed, total_s, rows = merge_records(args.json, args.scale,
                                                args.seed, total, records)
        with open(args.json, "w") as f:
            json.dump({"scale": args.scale, "seed": seed,
                       "total_seconds": total_s, "rows": rows}, f, indent=2)
        print(f"# wrote {args.json} ({len(rows)} rows)", file=sys.stderr)
    print(f"# total_seconds={total:.0f}", file=sys.stderr)


if __name__ == "__main__":
    main()
