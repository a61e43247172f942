"""The served engine's host phases as the benchmark sees them: the profiler
trace of a tick and a flush holds every ``repro.engine.*`` span, read by the
trace reduction's host-span reader, and ``bench/phases.py`` reports the
window's phase means, which with the generator's lateness add up to the
mean latency from due time."""
from __future__ import annotations

import jax
import numpy as np
import pytest

import bench_checkout as bc

SPANS = ("repro.engine.lock_wait", "repro.engine.tick",
         "repro.engine.tick.close", "repro.engine.refresh",
         "repro.engine.tick.events", "repro.engine.tick.ingest",
         "repro.engine.flush", "repro.engine.flush.part",
         "repro.engine.flush.stack", "repro.engine.flush.wait",
         "repro.engine.flush.resolve")


def test_trace_of_a_tick_and_a_flush_holds_every_span(tmp_path):
    import trace_reduce
    from jax.profiler import ProfileData

    from repro.core import AZURE_PRIORS, ZEROTH, geometric_grid, make_policy
    from repro.serve import Arrival, ExternalEvents, OnlineAdmissionEngine
    from repro.sim import SimConfig

    cfg = SimConfig(capacity=500.0, arrival_rate=0.08,
                    horizon_hours=6 * 24.0, dt=24.0, max_slots=32,
                    max_arrivals=4, d_points=8, priors=AZURE_PRIORS,
                    agg_refresh_steps=1)
    eng = OnlineAdmissionEngine(
        cfg, geometric_grid(24.0, 2160.0, 12), ZEROTH,
        make_policy(ZEROTH, threshold=cfg.capacity, capacity=cfg.capacity),
        micro_batch=4)
    zeros = np.zeros(cfg.max_slots, np.float32)
    events = ExternalEvents(core_deaths=zeros,
                            spont_death=np.zeros(cfg.max_slots, bool),
                            scaleout_cores=zeros, n_scaleouts=zeros)
    arrival = Arrival.draw(jax.random.PRNGKey(1), cfg)

    def tick_and_flush():
        eng.tick(events=events)
        fut = eng.submit(arrival)
        eng.flush()
        return fut.result(timeout=10)

    tick_and_flush()                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        tick_and_flush()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path)))
    names = {name for _, _, name in trace_reduce._host_spans(data.planes)}
    assert set(SPANS) <= names, sorted(set(SPANS) - names)


@pytest.fixture(scope="module")
def phased(tmp_path_factory):
    import phases
    import run

    root = bc.make_checkout(str(tmp_path_factory.mktemp("checkout")))
    args = run.parse(["--workload", bc.CELL, "--seed", "3000000023",
                      "--seconds", "2", "--trace", "0"])
    with bc.jax_settings_kept():
        return phases.run_phases(args, root=root, require_chip=False)


def test_phases_of_the_window(phased):
    from repro.serve import OnlineAdmissionEngine

    code, result, out = phased
    assert code == 0 and result["correct"], result["checks"]
    for key in ("queue_wait_ms", "answer_ms", "decide_wait_ms",
                "part_host_ms", "tick_host_ms", "lock_wait_ms",
                "lock_wait_ms_tick"):
        assert out[key] >= 0.0, key
    assert out["queue_wait_ms"] > 0.0 and out["answer_ms"] > 0.0
    assert 0.0 < out["flush_busy_pct"] < 100.0
    # the small cell is a fleet: no traced decide, every other step counted
    steps = set(OnlineAdmissionEngine.JIT_STEPS) - {"decide_traced"}
    assert set(out["compiled_in_window"]) == steps
    assert all(n >= 0 for n in out["compiled_in_window"].values())
    # a request's latency from due time is its lateness, queue wait and
    # answer time
    parts = out["late_ms_mean"] + out["queue_wait_ms"] + out["answer_ms"]
    assert parts == pytest.approx(out["latency_ms_mean"],
                                  abs=max(1.0, 0.05 * out["latency_ms_mean"]))


def test_phases_of_a_program_without_the_counters():
    import engine_counters
    from repro.obs import HostHistogram

    # what the engine's snapshot held before these counters
    old = {"n_flushes": 3, "pump_idle_fraction": 0.5,
           "flush_batch_size": HostHistogram((1.0, 8.0))}
    assert engine_counters.phases(old, old) == {}
    assert engine_counters.compiled(old, old) == {}
