#!/usr/bin/env python3
"""Find a served cell's knee: serve it at several offered rates, one process.

    python3 bench/knee_sweep.py --workload <cell> --seconds <s> --seed <n> \
        --windows-per-s 8 16 24 32

Each point is a whole run of the cell (set-up, fill, window, check) with
the traffic file's ``windows_per_s`` replaced; the offered rate is that
times the configuration's mean requests per window. Prints one JSON line
per point: offered and decided rates, p50/p95/p99 over the window and p99
of its first and last quarters (a backlog that grows shows as a last
quarter slower than the first), and whether the run was correct. The knee
is the highest rate whose p95 stays under the flush SLO with no growing
backlog; the cell's traffic file is then set to about 0.8 of it
(``PERF.md``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows-per-s", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import run

    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    jax = run.setup_jax()
    if jax.devices()[0].platform != "tpu":
        print("knee_sweep: needs a TPU", file=sys.stderr)
        return 1
    c = run.cell(run.benchmark(), args.workload)
    driver = run.load_module(os.path.join(BENCH, "drivers",
                                          c.traffic["driver"] + ".py"))
    mean = c.config["arrival_rate_per_h"] * c.config["dt_h"]
    for k, wps in enumerate(args.windows_per_s):
        traffic = dict(c.traffic, windows_per_s=wps)
        ctx = run.SimpleNamespace(
            config=c.config, traffic=traffic, seed=args.seed + k,
            seconds=args.seconds, trace=False, chips=1,
            t_start=time.perf_counter(), engine_hook=None,
            answer_wait_s=run.ANSWER_WAIT_S)
        out = driver.run(ctx)
        lat = out["latencies_ms"]
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "windows_per_s": wps, "offered_per_s": wps * mean,
            "decisions_per_s": out["e2e"]["decisions_per_s"],
            "p50_ms": out["e2e"]["decision_p50_ms"],
            "p95_ms": out["info"]["decision_p95_ms"],
            "p99_ms": out["info"]["decision_p99_ms"],
            "p99_first_quarter_ms": float(np.percentile(lat[:q], 99)),
            "p99_last_quarter_ms": float(np.percentile(lat[-q:], 99)),
            "correct": all(v["value"] <= v["limit"]
                           for v in out["checks"].values()),
            "checks": out["checks"], "info": out["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
