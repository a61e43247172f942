"""Online/offline equivalence and the micro-batching front-end.

The ``OnlineAdmissionEngine`` is built from the *same*
``make_admission_core`` functions the offline drivers scan — so feeding it
the exact event keys and arrival stream a ``make_run`` call draws must
reproduce the offline decisions and final metrics bit-for-bit. These tests
assert exactly that (single cluster tier-1; the quick-preset fleet variant
is slow-marked), plus the submit/flush future contract, the background
pump, observed-event ingestion, and the tuned-operating-point loader the
daemon depends on.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import (AZURE_PRIORS, SECOND, ZEROTH, fleet_policy,
                        geometric_grid, make_policy)
from repro.serve import (Arrival, ExternalEvents, OnlineAdmissionEngine,
                         default_policy_param, format_operating_derived,
                         load_operating_point, operating_row_name)
from repro.sim import (FleetConfig, LeastUtilizedRouter, RandomRouter,
                       SimConfig, draw_arrival_stream, make_fleet_run,
                       make_run)

CFG = SimConfig(capacity=500.0, arrival_rate=0.08, horizon_hours=30 * 24.0,
                dt=24.0, max_slots=96, max_arrivals=4, d_points=8,
                priors=AZURE_PRIORS, agg_refresh_steps=3)
GRID = geometric_grid(24.0, 3 * 30 * 24.0, 12)

SMALL = CFG._replace(horizon_hours=6 * 24.0, max_slots=32,
                     agg_refresh_steps=1)


def _offline_inputs(key, cfg):
    """Replicate make_run's key discipline: one stream draw, then one event
    key per step."""
    k_stream, k_scan = jax.random.split(key)
    stream = draw_arrival_stream(k_stream, cfg)
    keys = jax.random.split(k_scan, cfg.n_steps)
    return stream, keys


def _drive(engine, stream, keys):
    n_arr = np.asarray(stream.n_arrivals)
    n_lanes = stream.c0.shape[1]
    accepts = []
    for t in range(keys.shape[0]):
        engine.tick(keys[t])
        slice_t = jax.tree.map(lambda x: x[t], stream)
        accepts.append(engine.decide_slice(
            slice_t, np.arange(n_lanes) < n_arr[t]))
    return np.stack(accepts)


def _assert_metrics_equal(off, on):
    for name, val in off._asdict().items():
        got = getattr(on, name)
        if hasattr(val, "_asdict"):
            _assert_metrics_equal(val, got)
        else:
            np.testing.assert_array_equal(np.asarray(val), np.asarray(got),
                                          err_msg=name)


@pytest.mark.parametrize("k_refresh", [1, 3])
def test_single_cluster_matches_offline_bit_for_bit(k_refresh):
    """Every tick's close, dynamics and (on the K schedule) refresh, and
    every decide, against the offline scan: with K = 3 two ticks in three
    run without the refresh."""
    cfg = CFG._replace(agg_refresh_steps=k_refresh)
    pol = make_policy(SECOND, rho=0.05, capacity=cfg.capacity)
    key = jax.random.PRNGKey(1)
    m_off, acc_off = make_run(cfg, GRID, SECOND,
                              record_decisions=True)(key, pol)
    stream, keys = _offline_inputs(key, cfg)
    eng = OnlineAdmissionEngine(cfg, GRID, SECOND, pol)
    acc_on = _drive(eng, stream, keys)
    np.testing.assert_array_equal(acc_on, np.asarray(acc_off))
    _assert_metrics_equal(m_off, eng.metrics())


@pytest.mark.parametrize("router,k_refresh", [
    (RandomRouter(), 1),           # routes by the key the tick folds
    (LeastUtilizedRouter(), 3),
])
def test_small_fleet_matches_offline_bit_for_bit(router, k_refresh):
    """A two-cluster fleet against ``make_fleet_run``: the route key each
    tick folds on the device (``fold_in(step_key, C)``), the routed-nowhere
    and per-cluster counters the decides carry there, and the close inside
    the tick's program."""
    base = SMALL._replace(arrival_rate=0.3, agg_refresh_steps=k_refresh)
    fleet = FleetConfig(base=base, capacities=(300.0, 200.0))
    pol = fleet_policy(SECOND, capacities=fleet.capacities, rho=0.05)
    key = jax.random.PRNGKey(6)
    m_off, acc_off, _ = make_fleet_run(
        fleet, GRID, SECOND, router=router, record_decisions=True)(key, pol)
    stream, keys = _offline_inputs(key, base)
    eng = OnlineAdmissionEngine(fleet, GRID, SECOND, pol, router=router)
    acc_on = _drive(eng, stream, keys)
    np.testing.assert_array_equal(acc_on, np.any(np.asarray(acc_off), axis=1))
    _assert_metrics_equal(m_off, eng.metrics())
    assert np.asarray(acc_off).any()


@pytest.mark.slow
def test_fleet_quick_preset_matches_offline_bit_for_bit():
    # the quick preset's shapes (1536 slots, 32-point grid, K=8) over a
    # shortened horizon — heavy enough to exercise the vmapped fleet path
    # at production state size
    base = SimConfig(capacity=5_000.0, arrival_rate=0.25,
                     horizon_hours=40 * 12.0, dt=12.0, max_slots=1536,
                     max_arrivals=5, priors=AZURE_PRIORS,
                     agg_refresh_steps=8)
    fleet = FleetConfig(base=base, capacities=(3_000.0, 2_000.0))
    grid = geometric_grid(12.0, 3 * 40 * 12.0, 32)
    pol = fleet_policy(SECOND, capacities=fleet.capacities, rho=0.08)
    key = jax.random.PRNGKey(2)
    m_off, acc_off, _ = make_fleet_run(
        fleet, grid, SECOND, router=LeastUtilizedRouter(),
        record_decisions=True)(key, pol)
    stream, keys = _offline_inputs(key, base)
    eng = OnlineAdmissionEngine(fleet, grid, SECOND, pol,
                                router=LeastUtilizedRouter())
    acc_on = _drive(eng, stream, keys)
    np.testing.assert_array_equal(acc_on, np.any(np.asarray(acc_off), axis=1))
    _assert_metrics_equal(m_off, eng.metrics())


def test_submit_flush_matches_decide_slice():
    """The micro-batching front-end stacks submitted tickets onto exactly
    the decide_slice path: same arrivals, same decisions."""
    pol = make_policy(SECOND, rho=0.05, capacity=SMALL.capacity)
    key = jax.random.PRNGKey(3)
    stream, keys = _offline_inputs(key, SMALL)
    n_arr = np.asarray(stream.n_arrivals)
    n_lanes = stream.c0.shape[1]

    ref = OnlineAdmissionEngine(SMALL, GRID, SECOND, pol)
    acc_ref = _drive(ref, stream, keys)

    eng = OnlineAdmissionEngine(SMALL, GRID, SECOND, pol)
    for t in range(SMALL.n_steps):
        eng.tick(keys[t])
        futs = [eng.submit(Arrival.from_stream(stream, t, a))
                for a in range(min(int(n_arr[t]), n_lanes))]
        assert eng.n_pending == len(futs)
        eng.flush()
        got = [f.result() for f in futs]
        want = list(acc_ref[t][:len(futs)])
        assert got == [bool(w) for w in want]
    assert eng.decisions == int(np.minimum(n_arr, n_lanes).sum())


def test_background_pump_resolves_futures():
    pol = make_policy(ZEROTH, threshold=SMALL.capacity,
                      capacity=SMALL.capacity)
    eng = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, micro_batch=4)
    eng.tick(jax.random.PRNGKey(0))
    eng.start(interval_s=0.001)
    try:
        keys = jax.random.split(jax.random.PRNGKey(4), 10)
        futs = [eng.submit(Arrival.draw(k, SMALL)) for k in keys]
        results = [f.result(timeout=30) for f in futs]
    finally:
        eng.stop()
    assert all(isinstance(r, bool) for r in results)
    assert eng.decisions == len(futs)


@pytest.mark.parametrize("k_refresh", [1, 3])
def test_external_event_ingestion(k_refresh):
    """Production path: observed departures/scale-outs via tick(events=),
    with and without the refresh in the same tick (K = 3: the two event
    ticks refresh nothing)."""
    cfg = SMALL._replace(agg_refresh_steps=k_refresh)
    pol = make_policy(ZEROTH, threshold=cfg.capacity, capacity=cfg.capacity)
    eng = OnlineAdmissionEngine(cfg, GRID, ZEROTH, pol, micro_batch=4)
    eng.tick(jax.random.PRNGKey(0))
    fut = eng.submit(Arrival.draw(jax.random.PRNGKey(5), SMALL))
    eng.flush()
    assert fut.result() is True  # empty cluster, threshold = capacity

    s = SMALL.max_slots
    zeros = np.zeros(s, np.float32)
    no_deaths = np.zeros(s, bool)
    scaleout = zeros.copy()
    scaleout[0] = 5.0          # sequential placement: first slot
    n_req = zeros.copy()
    n_req[0] = 1.0
    eng.tick(events=ExternalEvents(core_deaths=zeros, spont_death=no_deaths,
                                   scaleout_cores=scaleout,
                                   n_scaleouts=n_req))
    m = eng.metrics()
    assert int(m.total_requests) == 1
    assert int(m.failed_requests) == 0
    assert int(m.alive_end) == 1

    kill = no_deaths.copy()
    kill[0] = True
    eng.tick(events=ExternalEvents(core_deaths=zeros, spont_death=kill,
                                   scaleout_cores=zeros, n_scaleouts=zeros))
    m = eng.metrics()
    assert int(m.alive_end) == 0
    assert int(m.n_departed) == 1
    assert int(m.total_requests) == 1        # no window closed twice
    assert eng.n_refreshes == (3 if k_refresh == 1 else 1)


def test_tick_and_flush_protocol_errors():
    pol = make_policy(SECOND, rho=0.05, capacity=SMALL.capacity)
    eng = OnlineAdmissionEngine(SMALL, GRID, SECOND, pol)
    with pytest.raises(RuntimeError):
        eng.flush()
    with pytest.raises(ValueError):
        eng.tick()
    with pytest.raises(ValueError):
        eng.tick(jax.random.PRNGKey(0),
                 events=ExternalEvents(*[np.zeros(SMALL.max_slots)] * 4))


def test_operating_point_roundtrip(tmp_path):
    rows = [
        {"name": operating_row_name("quick", "second"), "us_per_call": 0.0,
         "derived": format_operating_derived(0.08, 5_000.0, 5e-4)},
        {"name": operating_row_name("quick", "first"), "us_per_call": 0.0,
         "derived": format_operating_derived(1_850.0, 5_000.0, 5e-4)},
    ]
    path = tmp_path / "BENCH_quick.json"
    path.write_text(json.dumps({"scale": "quick", "rows": rows}))

    op = load_operating_point("second", "quick", bench_path=str(path))
    assert op.theta == 0.08 and op.capacity == 5_000.0 and op.tau == 5e-4
    # rho is scale-free; thresholds rescale linearly with capacity
    assert op.theta_for(1_000.0) == 0.08
    first = load_operating_point("first", "quick", bench_path=str(path))
    assert first.theta_for(1_000.0) == pytest.approx(370.0)

    assert default_policy_param("second", 1_000.0,
                                bench_path=str(path)) == 0.08
    missing = tmp_path / "nope.json"
    with pytest.warns(UserWarning, match="falling back"):
        param = default_policy_param("second", 1_000.0,
                                     bench_path=str(missing))
    assert param == 0.15
    with pytest.warns(UserWarning):
        param = default_policy_param("zeroth", 1_000.0,
                                     bench_path=str(missing))
    assert param == 700.0


# ---------------------------------------------------------------------------
# PR 9: sharded slot table, deadline-aware flush, concurrency bugfix pass
# ---------------------------------------------------------------------------


def _zero_events(cfg):
    s = cfg.max_slots
    return ExternalEvents(core_deaths=np.zeros(s, np.float32),
                          spont_death=np.zeros(s, bool),
                          scaleout_cores=np.zeros(s, np.float32),
                          n_scaleouts=np.zeros(s, np.float32))


def test_shards_validation_errors():
    from repro.sim import slot_mesh

    pol = make_policy(SECOND, rho=0.05, capacity=SMALL.capacity)
    # more shards than visible devices: actionable XLA_FLAGS guidance
    with pytest.raises(ValueError, match="host_platform_device_count"):
        OnlineAdmissionEngine(SMALL, GRID, SECOND, pol,
                              shards=jax.device_count() + 1)
    with pytest.raises(ValueError, match="n_shards"):
        slot_mesh(0)
    # fleet engines spread state over the cluster axis, not slot shards
    fleet = FleetConfig(base=SMALL, capacities=(300.0, 200.0))
    fpol = fleet_policy(SECOND, capacities=fleet.capacities, rho=0.05)
    with pytest.raises(ValueError, match="fleet"):
        OnlineAdmissionEngine(fleet, GRID, SECOND, fpol, shards=2)


def test_event_path_keys_derive_from_seed_chain():
    """Regression (PR 9): the observed-events tick path used to reseed with
    PRNGKey(self.ticks) — identical across engines and restarts. The key
    must now derive from the engine's seed chain: same seed => same chain,
    different seeds => diverging chains, and the chain advances per tick."""
    pol = make_policy(ZEROTH, threshold=SMALL.capacity,
                      capacity=SMALL.capacity)
    ev = _zero_events(SMALL)
    e_a = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, seed=0)
    e_b = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, seed=1)
    e_a2 = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, seed=0)
    for e in (e_a, e_b, e_a2):
        e.tick(events=ev)
    k_a, k_b, k_a2 = (np.asarray(e._win.key) for e in (e_a, e_b, e_a2))
    assert np.array_equal(k_a, k_a2)          # restart-reproducible
    assert not np.array_equal(k_a, k_b)       # engines decorrelate
    e_a.tick(events=ev)
    assert not np.array_equal(np.asarray(e_a._win.key), k_a)  # advances
    # the chain is fold_in(PRNGKey(seed), tick), folded inside the tick
    for t, k in enumerate((k_a, np.asarray(e_a._win.key))):
        np.testing.assert_array_equal(
            k, np.asarray(jax.random.fold_in(jax.random.PRNGKey(0), t)))


def test_close_window_counter_idempotence():
    """Regression (PR 9): _close_window now zeroes the window accept/reject
    accumulators after folding them, so metrics() twice in a row (or
    metrics() followed by tick()) cannot double-count decisions."""
    pol = make_policy(ZEROTH, threshold=SMALL.capacity,
                      capacity=SMALL.capacity)
    key = jax.random.PRNGKey(11)
    eng = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, micro_batch=4)
    eng.tick(jax.random.PRNGKey(0))
    futs = [eng.submit(Arrival.draw(k, SMALL))
            for k in jax.random.split(key, 3)]
    eng.flush()
    assert all(f.result() for f in futs)      # empty cluster, thr=capacity
    m1 = eng.metrics()
    m2 = eng.metrics()                        # second close: no-op
    assert int(m1.arrivals_accepted) == int(m2.arrivals_accepted) == 3
    eng.tick(jax.random.PRNGKey(1))
    m3 = eng.metrics()
    assert int(m3.arrivals_accepted) == 3     # tick didn't re-fold them


def test_flush_failure_resolves_futures_with_exception():
    """A decide chunk that raises must fail the queued futures instead of
    leaving callers blocked forever."""
    pol = make_policy(ZEROTH, threshold=SMALL.capacity,
                      capacity=SMALL.capacity)
    eng = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, micro_batch=2)
    eng.tick(jax.random.PRNGKey(0))
    futs = [eng.submit(Arrival.draw(k, SMALL))
            for k in jax.random.split(jax.random.PRNGKey(1), 3)]
    boom = RuntimeError("decide exploded")

    def bad_decide(arrivals):
        raise boom

    eng._decide = bad_decide
    with pytest.raises(RuntimeError, match="decide exploded"):
        eng.flush()
    for f in futs:
        assert f.done()
        with pytest.raises(RuntimeError, match="decide exploded"):
            f.result(timeout=0)


def test_deadline_scheduler_fires_partial_and_full_batches():
    """flush_slo_ms switches start() to the deadline scheduler: paced
    sub-width load resolves via the deadline trigger within the SLO (zero
    recorded misses after warmup), and a width-sized burst fires on the
    width trigger without waiting for any deadline."""
    import time as _time

    pol = make_policy(ZEROTH, threshold=SMALL.capacity,
                      capacity=SMALL.capacity)
    eng = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, micro_batch=4,
                                flush_slo_ms=500.0)
    eng.tick(jax.random.PRNGKey(0))
    eng._decide([Arrival.draw(jax.random.PRNGKey(1), SMALL)])  # compile
    eng.start()
    try:
        # deadline trigger: 2 < width requests, nothing else arrives
        futs = [eng.submit(Arrival.draw(k, SMALL))
                for k in jax.random.split(jax.random.PRNGKey(2), 2)]
        t0 = _time.monotonic()
        assert all(f.result(timeout=10) for f in futs)
        assert _time.monotonic() - t0 <= 0.5 + 5.0   # resolved near the SLO
        # width trigger: a full batch goes immediately
        futs = [eng.submit(Arrival.draw(k, SMALL))
                for k in jax.random.split(jax.random.PRNGKey(3), 4)]
        assert all(isinstance(f.result(timeout=10), bool) for f in futs)
    finally:
        eng.stop()
    snap = eng.metrics_snapshot()["engine"]
    assert snap["deadline_misses"] == 0
    assert snap["flush_slo_ms"] == 500.0
    assert snap["n_shards"] == 1
    assert snap["decision_latency_seconds"].total == 6
    with pytest.raises(ValueError, match="flush_slo_ms"):
        OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, flush_slo_ms=-1.0)


def test_concurrency_stress_ticker_pump_submitters():
    """Ticker thread + background pump + N submitter threads: no exception
    anywhere, every future resolves, and the decisions equal a serial
    replay (deterministic zero-event dynamics + threshold=capacity make the
    outcome interleaving-invariant: everything fits, everything admits)."""
    import threading

    pol = make_policy(ZEROTH, threshold=SMALL.capacity,
                      capacity=SMALL.capacity)
    eng = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, micro_batch=4)
    ev = _zero_events(SMALL)
    eng.tick(events=ev)
    n_sub, per_sub = 3, 8
    arrivals = [[Arrival.draw(jax.random.PRNGKey(100 * i + j), SMALL)
                 for j in range(per_sub)] for i in range(n_sub)]
    results: dict = {}
    errors: list = []
    stop_ticks = threading.Event()

    def ticker():
        try:
            while not stop_ticks.is_set():
                eng.tick(events=ev)
                eng.metrics_snapshot()        # scrape racing the pump
        except Exception as exc:              # pragma: no cover
            errors.append(exc)

    def submitter(i):
        try:
            futs = [eng.submit(a) for a in arrivals[i]]
            results[i] = [f.result(timeout=60) for f in futs]
        except Exception as exc:              # pragma: no cover
            errors.append(exc)

    eng.start(interval_s=0.0)
    threads = [threading.Thread(target=ticker)]
    threads += [threading.Thread(target=submitter, args=(i,))
                for i in range(n_sub)]
    for t in threads:
        t.start()
    for t in threads[1:]:
        t.join(timeout=120)
    stop_ticks.set()
    threads[0].join(timeout=120)
    eng.stop()
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)
    assert sorted(results) == list(range(n_sub))
    # serial replay: fresh engine, same arrivals, single thread
    ref = OnlineAdmissionEngine(SMALL, GRID, ZEROTH, pol, micro_batch=4)
    ref.tick(events=ev)
    for i in range(n_sub):
        futs = [ref.submit(a) for a in arrivals[i]]
        ref.flush()
        assert results[i] == [f.result() for f in futs]
    assert eng.decisions == n_sub * per_sub


@pytest.mark.slow
def test_sharded_engine_matches_unsharded_on_virtual_devices():
    """Tentpole acceptance (PR 9): on 8 virtual CPU devices, a shards=8
    engine is decision- and metric-equivalent — bit-for-bit — to the
    unsharded engine over the same stream, including the telemetry rider."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", """
import jax, numpy as np
from repro.core import AZURE_PRIORS, SECOND, geometric_grid, make_policy
from repro.serve import OnlineAdmissionEngine
from repro.sim import SimConfig, draw_arrival_stream

cfg = SimConfig(capacity=500.0, arrival_rate=0.08, horizon_hours=6*24.0,
                dt=24.0, max_slots=32, max_arrivals=4, d_points=8,
                priors=AZURE_PRIORS, agg_refresh_steps=1, telemetry=True)
grid = geometric_grid(24.0, 3*30*24.0, 12)
pol = make_policy(SECOND, rho=0.05, capacity=cfg.capacity)
k_stream, k_scan = jax.random.split(jax.random.PRNGKey(1))
stream = draw_arrival_stream(k_stream, cfg)
keys = jax.random.split(k_scan, cfg.n_steps)
n_arr = np.asarray(stream.n_arrivals)
n_lanes = stream.c0.shape[1]

def drive(engine):
    acc = []
    for t in range(keys.shape[0]):
        engine.tick(keys[t])
        sl = jax.tree.map(lambda x: x[t], stream)
        acc.append(engine.decide_slice(sl, np.arange(n_lanes) < n_arr[t]))
    return np.stack(acc), engine.metrics(), engine.metrics_snapshot()

assert jax.device_count() == 8
a1, m1, s1 = drive(OnlineAdmissionEngine(cfg, grid, SECOND, pol))
a8, m8, s8 = drive(OnlineAdmissionEngine(cfg, grid, SECOND, pol, shards=8))
np.testing.assert_array_equal(a1, a8)
for name in m1._fields:
    np.testing.assert_array_equal(np.asarray(getattr(m1, name)),
                                  np.asarray(getattr(m8, name)),
                                  err_msg=name)
assert s1['telemetry'] == s8['telemetry']
assert s8['engine']['n_shards'] == 8
print('OK', int(np.sum(a8)))
"""], env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr
    assert out.stdout.startswith("OK")
