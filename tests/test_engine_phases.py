"""The served engine's host phase counters: a request's queue wait and
answer time add up to its submit→answer time, each histogram counts what
was decided, the lock waits count every flush and tick, the compiled-program
counts name a recompile, every new family renders as Prometheus text, and
``HostHistogram.observe`` buckets as the linear scan it replaced did."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import AZURE_PRIORS, ZEROTH, geometric_grid, make_policy
from repro.obs import HostHistogram, log_buckets, snapshot_to_prometheus
from repro.serve import Arrival, OnlineAdmissionEngine
from repro.sim import ArrivalStream, SimConfig

from test_telemetry import _check_prometheus_text

CFG = SimConfig(capacity=500.0, arrival_rate=0.08, horizon_hours=6 * 24.0,
                dt=24.0, max_slots=32, max_arrivals=4, d_points=8,
                priors=AZURE_PRIORS, agg_refresh_steps=1)
GRID = geometric_grid(24.0, 3 * 30 * 24.0, 12)
POLICY = make_policy(ZEROTH, threshold=CFG.capacity, capacity=CFG.capacity)
#: the histogram families the engine added for the request's path
PHASES = ("queue_wait_seconds", "answer_seconds", "decide_wait_seconds",
          "tick_host_seconds")


def _arrivals(seed: int, n: int) -> list:
    return [Arrival.draw(k, CFG)
            for k in jax.random.split(jax.random.PRNGKey(seed), n)]


@pytest.fixture(scope="module")
def served():
    """A deadline-scheduled engine, warmed up, then fed six single requests
    one after another and one full batch, with a tick between them. Each
    single request's submit→answer time is bracketed by the caller's
    stamps, beside the engine's phase sums it added: at least from
    ``submit``'s return to the done-callback (when the callback ran inside
    the pump's ``set_result``), at most from before ``submit`` to the
    moment the answer counter is seen."""
    eng = OnlineAdmissionEngine(CFG, GRID, ZEROTH, POLICY, micro_batch=4,
                                flush_slo_ms=20.0)
    eng.tick(jax.random.PRNGKey(0))
    eng._decide(_arrivals(1, 1))                     # compile the decide
    eng.start()
    singles = []
    try:
        for i, arrival in enumerate(_arrivals(2, 6)):
            before = eng.metrics_snapshot()["engine"]
            answered = threading.Event()
            stamp = {}

            def done(_fut, stamp=stamp, answered=answered):
                stamp["t"] = time.monotonic()
                stamp["thread"] = threading.get_ident()
                answered.set()

            t_before = time.monotonic()
            fut = eng.submit(arrival)
            t_returned = time.monotonic()
            fut.add_done_callback(done)
            assert answered.wait(10.0)
            # the answer time is counted once set_result returns
            deadline = time.monotonic() + 10.0
            while True:
                after = eng.metrics_snapshot()["engine"]
                t_seen = time.monotonic()
                if (after["answer_seconds"].total
                        > before["answer_seconds"].total):
                    break
                assert time.monotonic() < deadline
                time.sleep(0.001)
            # a callback added after the answer runs here, late: no bound
            low = (stamp["t"] - t_returned
                   if stamp["thread"] != threading.get_ident() else 0.0)
            singles.append((before, after, low, t_seen - t_before))
            if i == 2:
                eng.tick(jax.random.PRNGKey(3))
        futs = [eng.submit(a) for a in _arrivals(4, 4)]
        assert all(isinstance(f.result(timeout=10), bool) for f in futs)
        deadline = time.monotonic() + 10.0
        while eng.metrics_snapshot()["engine"]["answer_seconds"].total < 10:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        snap = eng.metrics_snapshot()
    finally:
        eng.stop()
    return eng, singles, snap


def test_queue_wait_and_answer_add_up_to_each_request(served):
    _, singles, _ = served
    for before, after, low, high in singles:
        gained = {h: (after[h].total - before[h].total,
                      after[h].sum - before[h].sum)
                  for h in ("queue_wait_seconds", "answer_seconds")}
        assert all(n == 1 for n, _ in gained.values())
        total = sum(s for _, s in gained.values())
        # the engine stamps submit inside submit() and the answer after
        # set_result returns: inside the caller's brackets, within 1 ms
        assert low - 1e-3 <= total <= high + 1e-3, (low, total, high)
        assert after["time_s"] > before["time_s"]


def test_phase_histograms_count_what_was_decided(served):
    eng, _, snap = served
    e = snap["engine"]
    parts = e["flush_batch_size"].total
    assert e["queue_wait_seconds"].total == e["answer_seconds"].total == 10
    assert e["decision_latency_seconds"].total == 10
    # the warm-up decide is a part of its own, outside any flush
    assert e["decide_wait_seconds"].total == parts + 1
    assert e["tick_host_seconds"].total == e["n_ticks"] == 2
    assert e["part_host_seconds"] > 0.0
    assert e["decide_wait_seconds"].sum > 0.0
    waits = e["lock_wait_seconds"]
    assert waits["flush"]["count"] == e["n_flushes"]
    assert waits["tick"]["count"] == e["n_ticks"]
    assert all(w["sum"] >= 0.0 for w in waits.values())
    # every flush ran on the pump thread, inside the interval
    assert 0.0 < e["pump_busy_seconds"] < time.monotonic()
    for name in PHASES:
        assert e[name].buckets == e["decision_latency_seconds"].buckets
    assert eng.metrics_snapshot()["engine"]["n_requests"] == eng.decisions


def test_compiled_programs_name_a_recompile():
    eng = OnlineAdmissionEngine(CFG, GRID, ZEROTH, POLICY, micro_batch=4)

    def tick_and_flush(seed):
        eng.tick(jax.random.PRNGKey(seed))
        futs = [eng.submit(a) for a in _arrivals(seed, 2)]
        eng.flush()
        assert all(f.done() for f in futs)
        return eng.metrics_snapshot()["engine"]["compiled_programs"]

    tick_and_flush(0)
    warm = tick_and_flush(1)
    assert set(warm) == set(OnlineAdmissionEngine.JIT_STEPS)
    # the close runs inside the tick's program; its own only in metrics()
    assert warm["decide"] == warm["tick"] == 1 and warm["close"] == 0
    assert tick_and_flush(2) == warm
    arrivals = _arrivals(3, 8)
    stack = lambda *xs: np.stack([np.float32(x) for x in xs])
    wide = ArrivalStream(
        params=jax.tree.map(stack, *[a.params for a in arrivals]),
        c0=stack(*[a.c0 for a in arrivals]),
        bel=jax.tree.map(stack, *[a.bel for a in arrivals]),
        bel_alt=jax.tree.map(stack, *[a.bel_alt for a in arrivals]),
        n_arrivals=np.ones(8, np.int32))
    eng.decide_slice(wide, np.ones(8, bool))
    after = eng.metrics_snapshot()["engine"]["compiled_programs"]
    assert after == dict(warm, decide=warm["decide"] + 1)
    eng.metrics()
    after = eng.metrics_snapshot()["engine"]["compiled_programs"]
    assert after == dict(warm, decide=warm["decide"] + 1, close=1)


def test_every_new_family_renders_with_its_type(served):
    _, _, snap = served
    text = snapshot_to_prometheus(snap)
    fams = _check_prometheus_text(text)
    want = {
        "repro_admission_queue_wait_seconds": "histogram",
        "repro_admission_answer_seconds": "histogram",
        "repro_admission_decide_wait_seconds": "histogram",
        "repro_admission_tick_host_seconds": "histogram",
        "repro_admission_part_host_seconds_total": "counter",
        "repro_admission_lock_wait_seconds_total": "counter",
        "repro_admission_lock_acquires_total": "counter",
        "repro_admission_pump_busy_seconds_total": "counter",
        "repro_admission_compiled_programs": "gauge",
        "repro_admission_device_calls_total": "counter",
        "repro_admission_device_call_steps_total": "counter",
    }
    for name, mtype in want.items():
        assert fams.get(name) == mtype, name
    assert "repro_admission_pump_idle_fraction" not in fams
    e = snap["engine"]
    for taker in ("flush", "tick"):
        assert (f'repro_admission_lock_acquires_total{{taker="{taker}"}} '
                f'{e["lock_wait_seconds"][taker]["count"]}\n') in text
    assert ('repro_admission_compiled_programs{step="decide"} '
            f'{e["compiled_programs"]["decide"]}\n') in text
    calls = e["device_calls_total"]
    assert calls["part"]["sum"] == 3 * calls["part"]["count"] > 0
    for step, c in calls.items():
        assert (f'repro_admission_device_calls_total{{step="{step}"}} '
                f'{c["sum"]}\n') in text
        assert (f'repro_admission_device_call_steps_total{{step="{step}"}} '
                f'{c["count"]}\n') in text
    assert (f'repro_admission_queue_wait_seconds_count '
            f'{e["queue_wait_seconds"].total}\n') in text


def test_shutdown_log_line_reduces_every_histogram(served):
    import json

    from repro.launch.admission_daemon import snapshot_log_line

    _, _, snap = served
    eng = json.loads(snapshot_log_line(snap))["engine"]
    e = snap["engine"]
    for name in PHASES:
        mean = e[name].sum / e[name].total
        assert eng[name.replace("_seconds", "_mean_s")] == round(mean, 6)
    assert eng["compiled_programs"] == e["compiled_programs"]


def _scan_bucket(edges, value):
    """The linear scan ``observe`` used before: the first edge >= value."""
    for i, edge in enumerate(edges):
        if value <= edge:
            return i
    return len(edges)


@pytest.mark.parametrize("edges", [
    (0.1, 1.0),
    log_buckets(0.05 / 512.0, 0.05, 10) + (0.1, 0.2),
    log_buckets(1.0, 8.0, 8),
])
def test_histogram_bisect_matches_the_linear_scan(edges):
    edges = tuple(float(e) for e in edges)
    mids = [(a + b) / 2.0 for a, b in zip(edges, edges[1:])]
    values = ([0.0, -1.0, edges[0] / 2.0, 2.0 * edges[-1], float("inf"),
               float("nan")]
              + list(edges) + mids
              + [np.nextafter(e, np.inf) for e in edges]
              + [np.nextafter(e, -np.inf) for e in edges])
    for v in values:
        h = HostHistogram(edges)
        h.observe(v)
        want = [0] * (len(edges) + 1)
        want[_scan_bucket(edges, float(v))] = 1
        assert h.counts == want, v
