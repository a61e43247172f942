#!/usr/bin/env python3
"""The control of a tenant cell's comparison: ``bench/control.py``'s, with
``reference_tenant.TenantReplay`` rounded to ``--dtype`` in the program's
place, deciding every request and keeping its own tenant table.

    python3 bench/control_tenant.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 [--dtype bfloat16]

The run is ``control.py``'s on a private copy of it whose reference is the
tenant reference and whose requests are the tenant cell's
(``drivers/served_tenant.draw_arrivals``); its answers and its table are
held to the float64 reference by the comparison a tenant run is held to
(``drivers/served_tenant.check``). One JSON line per seed; its readings
set the tenant cell's limits (``PERF.md``).
"""
from __future__ import annotations

import importlib.util
import os
import sys
import time
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import generator  # noqa: E402
import reference  # noqa: E402
import reference_tenant  # noqa: E402


def _private_control():
    spec = importlib.util.spec_from_file_location(
        "control_for_tenants", os.path.join(BENCH, "control.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


control = _private_control()


def control_run(config: dict, traffic: dict, seed: int, seconds: float,
                dtype: str) -> dict:
    from drivers import served_tenant

    replays = []

    def replay(cfg, q):
        replays.append(reference_tenant.TenantReplay(cfg, q))
        return replays[-1]

    control.reference = SimpleNamespace(Replay=replay, BELIEF=("tenant",),
                                        rounding=reference.rounding)
    sched = generator.schedule(config, traffic, seed, seconds)
    host = served_tenant.draw_arrivals(config, int(sched.first[-1]), seed)
    t0 = time.perf_counter()
    rec, traces, issued = control.control_answers(config, sched, host, seed,
                                                  dtype)
    checks, info = served_tenant.check(
        config, traffic, sched, host, issued, rec, sched.n_windows, seed,
        traces, table=replays[0].table)
    info["seconds"] = time.perf_counter() - t0
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    return {"seed": seed, "dtype": dtype, "correct": correct,
            "checks": checks, "info": info}


control.control_run = control_run

if __name__ == "__main__":
    sys.exit(control.main())
