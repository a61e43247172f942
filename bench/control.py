#!/usr/bin/env python3
"""The control of a served cell's comparison: the reference, put in the
program's place and computed in a lower precision, must come out not correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--dtype bfloat16]

For each seed it draws the cell's own traffic (``generator``), lets
``reference.Replay`` with every intermediate rounded to ``--dtype`` decide
every request (each window's requests in flushes of the micro-batch width,
in order), with the events of the deployments it admitted
(``generator.World``), and holds those answers to the float64 reference by
the same comparison a run is held to (``drivers/served.check``). It prints one
JSON line per seed with the numbers compared and their limits. With
``--dtype float64`` the answers are the reference's own and must compare
exactly. The benchmark's runs never run it; its readings set the limits
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

import generator  # noqa: E402
import reference  # noqa: E402


def _control_window(rep, sched, w, world, arr, width, rec, part):
    rep.tick(world.next_events(rec), aggregate=True)
    rows = np.arange(sched.first[w], sched.first[w + 1])
    for j in range(0, len(rows), width):
        chunk = rows[j:j + width]
        batch = [{k: arr[k][i] for k in ("c0",) + reference.BELIEF}
                 for i in chunk]
        verdicts = rep.flush(batch)
        part += len(chunk)
        for i, v in zip(chunk.tolist(), verdicts):
            rec.record(i, w, part, v.admit)
    return part


def control_answers(config: dict, sched, host_arrivals: dict, seed: int,
                    dtype: str):
    """Every request decided by the reference rounded to ``dtype``; returns
    the answers, the traces and the events issued."""
    rep = reference.Replay(config, reference.rounding(dtype))
    world = generator.World(config, host_arrivals, seed)
    width = int(config["serving"]["micro_batch"])
    n = int(sched.first[-1])
    rec = generator.DecisionLog(n)
    rec.submitted = np.arange(n)
    arr = {k: np.asarray(v, np.float32).astype(np.float64)
           for k, v in host_arrivals.items()}
    part = 0
    with np.errstate(all="ignore"):      # bfloat16 overflows, as it would
        for w in range(sched.n_windows):
            part = _control_window(rep, sched, w, world, arr, width,
                                   rec, part)
    rep.finish()
    traces = (np.stack(rep.util_trace, axis=1),
              np.stack(rep.fail_trace, axis=1), rep.accepted, rep.rejected)
    return rec, traces, world.issued


def control_run(config: dict, traffic: dict, seed: int, seconds: float,
                dtype: str) -> dict:
    from drivers.served import check

    sched = generator.schedule(config, traffic, seed, seconds)
    host_arrivals = generator.draw_arrivals(config, int(sched.first[-1]),
                                            seed)
    t0 = time.perf_counter()
    rec, traces, issued = control_answers(config, sched, host_arrivals,
                                          seed, dtype)
    checks, info = check(config, traffic, sched, host_arrivals, issued,
                         rec, sched.n_windows, seed, traces)
    info["seconds"] = time.perf_counter() - t0
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    return {"seed": seed, "dtype": dtype, "correct": correct,
            "checks": checks, "info": info}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    import run

    c = run.cell(run.benchmark(), args.workload)
    for seed in args.seeds:
        print(json.dumps(control_run(c.config, c.traffic, seed, args.seconds,
                                     args.dtype)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
