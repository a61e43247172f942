#!/usr/bin/env python3
"""Faults planted in a served cell's timed path, each of which the comparison
that decides ``correct`` must catch.

    python3 bench/faults.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--faults state_unchanged half_of_each_batch ...]

Each fault is a whole run of the cell (set-up, window, check) with the
engine broken underneath; one JSON line per fault and seed gives the numbers
compared, their limits and whether the run came out correct. The
benchmark's runs never plant them; their readings are in ``PERF.md``.
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


def state_unchanged(engine):
    """Every tick returns the clusters' state as it found it."""
    import jax
    import jax.numpy as jnp

    ingest = engine._j_ingest
    engine._j_ingest = lambda caps, cs, ev: (
        cs, ingest(caps, jax.tree.map(jnp.copy, cs), ev)[1])


def half_of_each_batch(engine):
    """The second half of each flush part is rejected undecided."""
    decide = engine._decide

    def half(arrivals):
        k = (len(arrivals) + 1) // 2
        return np.concatenate([decide(arrivals[:k]),
                               np.zeros(len(arrivals) - k, bool)])

    engine._decide = half


def answer_altered(engine):
    """Every fifth flush part's first answer is flipped where it is made."""
    decide = engine._decide
    calls = [0]

    def altered(arrivals):
        out = np.array(decide(arrivals))
        calls[0] += 1
        if calls[0] % 5 == 0:
            out[0] = not out[0]
        return out

    engine._decide = altered


def always_reject(engine):
    """Every request is rejected undecided."""
    engine._decide = lambda arrivals: np.zeros(len(arrivals), bool)


FAULTS = {f.__name__: f for f in (state_unchanged, half_of_each_batch,
                                  answer_altered, always_reject)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=list(FAULTS),
                    choices=list(FAULTS))
    args = ap.parse_args(argv)
    import run

    for name in args.faults:
        for seed in args.seeds:
            a = run.parse(["--workload", args.workload, "--seed", str(seed),
                           "--seconds", str(args.seconds)])
            code, result = run.run_cell(a, engine_hook=FAULTS[name],
                                        t_start=time.perf_counter())
            if result is None:
                return code
            print(json.dumps({"fault": name, "seed": seed,
                              "correct": result["correct"],
                              "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
