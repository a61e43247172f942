#!/usr/bin/env python3
"""Run one served cell as ``run.py`` does, and add the engine's host phases
over the window.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints what ``run.py`` prints, and before its result line one line
``{"phases": ...}``: the means of ``engine_counters.phases`` (queue wait,
answer time, decide wait, the rest of each flush part, tick lock hold, lock
waits, the flush thread's busy share) between the engine's snapshots taken
after its pump starts and after it stops, the programs each jitted step
compiled in the window, and the mean latency from due time and generator
lateness of the window's requests. A request's latency is its lateness plus
its queue wait plus its answer time, so ``latency_ms_mean`` should equal
``late_ms_mean + queue_wait_ms + answer_ms``. On a program without the
phase counters only the latency and lateness are given.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import engine_counters  # noqa: E402
import run  # noqa: E402


def run_phases(args, *, root: str = run.ROOT, require_chip: bool = True,
               t_start: float = T_START):
    """``run.run_cell`` with the engine's snapshots and the driver's
    latencies watched; returns ``(exit code, result line or None, phases
    or None)``. The snapshots come through ``engine_hook``; the latencies,
    which no hook reaches, by wrapping the driver module's ``latencies``
    as ``run.load_module`` loads it."""
    snaps, seen = [], {}

    def watch_engine(engine):
        start, stop = engine.start, engine.stop

        def start_then_snap(*a, **k):
            start(*a, **k)
            snaps.append(engine.metrics_snapshot()["engine"])

        def stop_then_snap():
            stop()
            snaps.append(engine.metrics_snapshot()["engine"])

        engine.start, engine.stop = start_then_snap, stop_then_snap

    load = run.load_module

    def load_watched(path):
        mod = load(path)
        inner = getattr(mod, "latencies", None)
        if inner is not None:
            def latencies(*a):
                seen["lat"], seen["late"] = inner(*a)
                return seen["lat"], seen["late"]
            mod.latencies = latencies
        return mod

    run.load_module = load_watched
    try:
        code, result = run.run_cell(args, root=root,
                                    require_chip=require_chip,
                                    engine_hook=watch_engine, t_start=t_start)
    finally:
        run.load_module = load
    if result is None:
        return code, None, None
    before, after = snaps
    out = engine_counters.phases(before, after)
    out["compiled_in_window"] = engine_counters.compiled(before, after)
    out["latency_ms_mean"] = float(seen["lat"].mean())
    out["late_ms_mean"] = float(seen["late"].mean())
    return code, result, out


def main(argv=None) -> int:
    args = run.parse(argv)
    code, result, out = run_phases(args)
    if result is not None:
        print(json.dumps({"phases": out}), flush=True)
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
