"""The device calls of each engine step: a tick is its refresh when one is
due, one packed copy in and one program; a flush part is one packed copy
in, one program and one copy out. The engine's ``device_calls_total``
counter says so, a profiler trace of the step counts the same executions
and copies, and no step makes an implicit transfer
(``jax.transfer_guard("disallow")``) — for a single-cluster engine, a
fleet, and an engine sharded over two virtual CPU devices.

The CPU backend's transfer guard stops implicit host-to-device copies
only: reading a CPU array into numpy moves nothing, so the guard lets it
pass. The trace shows a fetch as one ``np.asarray(jax.Array)`` event when
it goes through ``jax.device_get`` or reads a sharded array; a bare
``np.asarray`` of a one-device CPU array leaves no event, so of an
implicit fetch only the sharded engine's count can tell."""
import collections
import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (AZURE_PRIORS, SECOND, fleet_policy, geometric_grid,
                        make_policy)
from repro.serve import Arrival, ExternalEvents, OnlineAdmissionEngine
from repro.sim import FleetConfig, LeastUtilizedRouter, SimConfig

#: refresh every other tick, so ticks with and without it are both counted
CFG = SimConfig(capacity=500.0, arrival_rate=0.08, horizon_hours=6 * 24.0,
                dt=24.0, max_slots=32, max_arrivals=4, d_points=8,
                priors=AZURE_PRIORS, agg_refresh_steps=2)
GRID = geometric_grid(24.0, 3 * 30 * 24.0, 12)
WIDTH = 4
#: profiler events on the dispatching thread, one per execution or copy
EVENTS = {"programs": "PjRtCpuExecutable::Execute",
          "copies_in": "DevicePutWithSharding",
          "copies_out": "np.asarray(jax.Array)"}


def make_engine(kind: str) -> OnlineAdmissionEngine:
    pol = make_policy(SECOND, rho=0.05, capacity=CFG.capacity)
    if kind == "fleet":
        fleet = FleetConfig(base=CFG, capacities=(300.0, 200.0))
        return OnlineAdmissionEngine(
            fleet, GRID, SECOND,
            fleet_policy(SECOND, capacities=fleet.capacities, rho=0.05),
            router=LeastUtilizedRouter(), micro_batch=WIDTH)
    return OnlineAdmissionEngine(CFG, GRID, SECOND, pol, micro_batch=WIDTH,
                                 shards=2 if kind == "sharded" else None)


def _events(eng) -> ExternalEvents:
    shape = (eng.n_c, CFG.max_slots) if eng.fleet else (CFG.max_slots,)
    cores = np.zeros(shape, np.float32)
    n_req = np.zeros(shape, np.float32)
    cores[..., 0], n_req[..., 0] = 2.0, 1.0      # a scale-out of slot 0
    return ExternalEvents(core_deaths=np.zeros(shape, np.float32),
                          spont_death=np.zeros(shape, bool),
                          scaleout_cores=cores, n_scaleouts=n_req)


def _traced(fn, path: str) -> dict:
    """``fn()`` under the profiler: its executions and copies, by kind."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(path)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                              recursive=True))[-1]
    names = collections.Counter(
        e.name for plane in ProfileData.from_file(xplane).planes
        if plane.name == "/host:CPU" for line in plane.lines
        for e in line.events)
    return {kind: names[name] for kind, name in EVENTS.items()}


def check_device_calls(kind: str, path: str) -> None:
    """Warm an engine up, then count a tick with the refresh, a tick
    without it, a flush part and a tick by key: by the engine's counter,
    by the trace, under the transfer guard."""
    eng = make_engine(kind)
    events = _events(eng)
    arrivals = [Arrival.draw(k, CFG)
                for k in jax.random.split(jax.random.PRNGKey(1), WIDTH)]
    key = jax.device_put(jax.random.PRNGKey(2), eng._sharding)

    def part():
        futs = [eng.submit(a) for a in arrivals]
        assert eng.flush() == WIDTH
        assert all(isinstance(f.result(timeout=0), bool) for f in futs)

    for _ in range(2):                    # compile every step
        eng.tick(events=events)
        part()
        eng.tick(key)
        part()
    assert eng.ticks % CFG.agg_refresh_steps == 0
    steps = (
        ("tick", lambda: eng.tick(events=events),
         dict(programs=2, copies_in=1, copies_out=0)),
        ("tick", lambda: eng.tick(events=events),
         dict(programs=1, copies_in=1, copies_out=0)),
        ("part", part, dict(programs=1, copies_in=1, copies_out=1)),
        ("tick", lambda: eng.tick(key),
         dict(programs=2, copies_in=0, copies_out=0)),
        ("tick", lambda: eng.tick(key),
         dict(programs=1, copies_in=0, copies_out=0)),
    )
    for i, (step, fn, want) in enumerate(steps):
        before = eng.metrics_snapshot()["engine"]["device_calls_total"]
        with jax.transfer_guard("disallow"):
            seen = _traced(fn, os.path.join(path, str(i)))
        after = eng.metrics_snapshot()["engine"]["device_calls_total"]
        assert seen == want, (kind, i, seen)
        assert after[step]["count"] == before[step]["count"] + 1, (kind, i)
        assert (after[step]["sum"] - before[step]["sum"]
                == sum(want.values())), (kind, i)
    snap = eng.metrics_snapshot()["engine"]
    # the window's steps compiled nothing new
    assert all(n <= 1 for n in snap["compiled_programs"].values()), snap


@pytest.mark.parametrize("kind", ["single", "fleet"])
def test_each_step_makes_its_counted_device_calls(kind, tmp_path):
    check_device_calls(kind, str(tmp_path))


def test_each_step_of_a_sharded_engine_makes_its_counted_device_calls(
        tmp_path):
    """The same count for an engine whose slot table is sharded over two
    virtual CPU devices (a process of its own: the device count is fixed
    when JAX starts)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(here, "..", "src"),
                                         here])
    out = subprocess.run([sys.executable, "-c", f"""
import jax
import test_engine_device_calls as t
assert jax.device_count() == 2
t.check_device_calls("sharded", {str(tmp_path)!r})
print("OK")
"""], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("OK")
