"""Telemetry layer: device counters, tracing, export, and the obs plumbing.

The observability contract is "free when off, invisible when on":

  * ``SimConfig(telemetry=False)`` (the default) compiles the rider out —
    the goldens pinned by ``tests/test_admission_core.py`` keep passing
    unchanged, which is the off-side proof.
  * ``telemetry=True`` must leave every decision and metric **bit-for-bit**
    identical to the committed goldens (asserted here against
    ``tests/data/golden_sim_metrics.npz``) while the rider's counters obey
    exact conservation laws (admits + rejects == routed == decided;
    histogram mass == event count).

Also covered: the online engine's non-blocking ``metrics_snapshot`` and its
offline equivalence, JSONL decision tracing, Prometheus text exposition
validity, the ``/metrics`` HTTP server, the daemon's SIGTERM graceful
shutdown (subprocess), the shared ``repro.obs.log`` logger, and the
vectorized ``bca_ci`` fast path (satellite of the same PR).
"""
import functools
import json
import logging
import os
import re
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.core import (AZURE_PRIORS, SECOND, ZEROTH, fleet_policy,
                        geometric_grid, make_policy)
from repro.obs import (DecisionTracer, HostHistogram, Metric, MetricsServer,
                       get_logger, render_prometheus, snapshot_to_prometheus,
                       telemetry_summary)
from repro.serve import Arrival, OnlineAdmissionEngine
from repro.sim import (FleetConfig, LeastUtilizedRouter, SimConfig,
                       draw_arrival_stream, make_fleet_run, make_run)
from repro.sim.metrics import bca_ci, weighted_mean
from repro.testing import given, settings, strategies

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "golden_sim_metrics.npz")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the golden configs of tests/test_admission_core.py, telemetry switched on
CFG = SimConfig(capacity=500.0, arrival_rate=0.08, horizon_hours=30 * 24.0,
                dt=24.0, max_slots=96, max_arrivals=4, d_points=8,
                priors=AZURE_PRIORS)
GRID = geometric_grid(24.0, 3 * 30 * 24.0, 12)
FLEET2 = FleetConfig(base=CFG._replace(telemetry=True),
                     capacities=(300.0, 200.0))

SMALL = CFG._replace(horizon_hours=6 * 24.0, max_slots=32,
                     agg_refresh_steps=3, telemetry=True)


def _flat(prefix, metrics):
    out = {}
    for name, val in metrics._asdict().items():
        if hasattr(val, "_asdict"):
            out.update(_flat(f"{prefix}/{name}", val))
        else:
            out[f"{prefix}/{name}"] = np.asarray(val)
    return out


def _assert_conservation(s, m, *, n_windows, n_refreshes=None):
    """The exact counting laws every telemetry summary must satisfy."""
    decided = s["n_admit"] + s["n_reject_capacity"] + s["n_reject_policy"]
    assert decided == s["n_routed"]
    assert s["n_admit"] == float(np.sum(m.arrivals_accepted))
    assert decided == float(np.sum(m.arrivals_accepted)
                            + np.sum(m.arrivals_rejected))
    assert sum(s["staleness_hist"]) == s["n_routed"]
    assert s["n_windows"] == n_windows
    assert sum(s["occupancy_hist"]) == n_windows
    assert sum(s["headroom_hist"]) == n_windows
    if n_refreshes is not None:
        assert s["n_refreshes"] == n_refreshes
    assert 0 < s["arr_placed"] <= s["n_admit"]
    assert s["arr_c0_mean"] > 0 and s["arr_c0_var"] >= 0


# ---------------------------------------------------------------------------
# telemetry on == goldens, bit for bit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tel_runs():
    """The two single-cluster golden runs, rerun with telemetry enabled."""
    cfg0 = CFG._replace(telemetry=True)
    m0, tel0 = make_run(cfg0, GRID, ZEROTH)(
        jax.random.PRNGKey(0),
        make_policy(ZEROTH, threshold=300.0, capacity=CFG.capacity))
    cfg3 = CFG._replace(agg_refresh_steps=3, telemetry=True)
    m3, tel3 = make_run(cfg3, GRID, SECOND)(
        jax.random.PRNGKey(1),
        make_policy(SECOND, rho=0.05, capacity=CFG.capacity))
    return (m0, tel0), (m3, tel3)


def test_single_cluster_golden_bit_for_bit_with_telemetry(tel_runs):
    (m0, _), (m3, _) = tel_runs
    arrays = {}
    arrays.update(_flat("single/zeroth", m0))
    arrays.update(_flat("single/second_k3", m3))
    gold = np.load(GOLDEN)
    checked = 0
    for name in gold.files:
        if name.startswith("single/"):
            np.testing.assert_array_equal(gold[name], arrays[name],
                                          err_msg=name)
            checked += 1
    assert checked >= 20


def test_counter_conservation_on_golden_runs(tel_runs):
    (m0, tel0), (m3, tel3) = tel_runs
    s0 = telemetry_summary(tel0)
    _assert_conservation(s0, m0, n_windows=CFG.n_steps,
                         n_refreshes=CFG.n_steps)  # K=1: refresh every step
    s3 = telemetry_summary(tel3)
    _assert_conservation(s3, m3, n_windows=CFG.n_steps,
                         n_refreshes=CFG.n_steps // 3)


def test_decisions_identical_on_off():
    cfg = CFG._replace(agg_refresh_steps=3)
    pol = make_policy(SECOND, rho=0.05, capacity=cfg.capacity)
    key = jax.random.PRNGKey(1)
    m_off, acc_off = make_run(cfg, GRID, SECOND,
                              record_decisions=True)(key, pol)
    m_on, acc_on, tel = make_run(cfg._replace(telemetry=True), GRID, SECOND,
                                 record_decisions=True)(key, pol)
    np.testing.assert_array_equal(np.asarray(acc_off), np.asarray(acc_on))
    for name, val in m_off._asdict().items():
        np.testing.assert_array_equal(np.asarray(val),
                                      np.asarray(getattr(m_on, name)),
                                      err_msg=name)
    assert telemetry_summary(tel)["n_admit"] == float(
        np.sum(np.asarray(acc_on)))


@functools.lru_cache(maxsize=1)
def _tel_run():
    cfg = CFG._replace(agg_refresh_steps=3, telemetry=True)
    return cfg, make_run(cfg, GRID, SECOND), make_policy(
        SECOND, rho=0.05, capacity=cfg.capacity)


@settings(max_examples=6, deadline=None)
@given(seed=strategies.integers(min_value=0, max_value=255))
def test_counter_conservation_property(seed):
    """Conservation holds at any seed, not just the golden keys (one
    compile, reused across examples)."""
    cfg, run, pol = _tel_run()
    m, tel = run(jax.random.PRNGKey(seed), pol)
    _assert_conservation(telemetry_summary(tel), m, n_windows=cfg.n_steps,
                         n_refreshes=cfg.n_steps // 3)


@pytest.mark.slow
def test_fleet_golden_bit_for_bit_with_telemetry():
    m, tel = make_fleet_run(FLEET2, GRID, SECOND,
                            router=LeastUtilizedRouter())(
        jax.random.PRNGKey(2),
        fleet_policy(SECOND, capacities=FLEET2.capacities, rho=0.05))
    arrays = _flat("fleet2/second", m)
    gold = np.load(GOLDEN)
    for name in gold.files:
        if name.startswith("fleet2/"):
            np.testing.assert_array_equal(gold[name], arrays[name],
                                          err_msg=name)
    s = telemetry_summary(tel)
    _assert_conservation(s, m.per_cluster,
                         n_windows=CFG.n_steps * FLEET2.n_clusters)
    pc = s["per_cluster"]
    assert sum(pc["n_routed"]) == s["n_routed"]
    assert sum(pc["n_admit"]) == s["n_admit"]


# ---------------------------------------------------------------------------
# online engine: snapshot, offline equivalence, tracing
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_run(tmp_path_factory):
    """Drive the engine over make_run's exact stream/keys with telemetry and
    a tracer attached; return everything the assertions below pick over."""
    pol = make_policy(SECOND, rho=0.05, capacity=SMALL.capacity)
    key = jax.random.PRNGKey(11)
    m_off, tel_off = make_run(SMALL, GRID, SECOND)(key, pol)
    k_stream, k_scan = jax.random.split(key)
    stream = draw_arrival_stream(k_stream, SMALL)
    keys = jax.random.split(k_scan, SMALL.n_steps)

    trace_path = tmp_path_factory.mktemp("obs") / "decisions.jsonl"
    tracer = DecisionTracer(trace_path)
    eng = OnlineAdmissionEngine(SMALL, GRID, SECOND, pol, tracer=tracer)
    n_arr = np.asarray(stream.n_arrivals)
    n_lanes = stream.c0.shape[1]
    for t in range(SMALL.n_steps):
        eng.tick(keys[t])
        futs = [eng.submit(Arrival.from_stream(stream, t, a))
                for a in range(min(int(n_arr[t]), n_lanes))]
        eng.flush()
        for f in futs:
            f.result()
    snap = eng.metrics_snapshot()
    tracer.close()
    return eng, m_off, tel_off, snap, trace_path


def test_engine_telemetry_matches_offline_bit_for_bit(engine_run):
    eng, m_off, tel_off, snap, _ = engine_run
    m_on = eng.metrics()
    for name, val in m_off._asdict().items():
        np.testing.assert_array_equal(np.asarray(val),
                                      np.asarray(getattr(m_on, name)),
                                      err_msg=name)
    off_leaves = jax.tree.leaves(tel_off)
    on_leaves = jax.tree.leaves(eng._cs.tel)
    for a, b in zip(off_leaves, on_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_snapshot_counters(engine_run):
    eng, m_off, tel_off, snap, _ = engine_run
    e = snap["engine"]
    assert e["n_ticks"] == SMALL.n_steps
    assert e["n_requests"] == eng.decisions
    assert e["n_refreshes"] == SMALL.n_steps // SMALL.agg_refresh_steps
    assert e["queue_depth"] == 0
    lat = e["decision_latency_seconds"]
    assert lat.total == eng.decisions
    assert lat.sum > 0 and lat.percentile(0.99) >= lat.percentile(0.5) >= 0
    batch = e["flush_batch_size"]
    assert batch.sum == lat.total  # sum of batch sizes == total decisions
    s = snap["telemetry"]
    _assert_conservation(s, m_off, n_windows=SMALL.n_steps)
    assert s == telemetry_summary(tel_off)


def test_engine_tracer_writes_jsonl(engine_run):
    eng, _, _, _, trace_path = engine_run
    lines = trace_path.read_text().splitlines()
    assert len(lines) == eng.decisions
    recs = [json.loads(ln) for ln in lines]
    for r in recs:
        assert set(r) >= {"step", "req_id", "policy_kind", "verdict",
                          "latency_s", "batch_size", "threshold", "score"}
        assert isinstance(r["verdict"], bool)
        assert r["latency_s"] >= 0.0
    assert [r["req_id"] for r in recs] == list(range(1, len(recs) + 1))
    n_admit = sum(r["verdict"] for r in recs)
    assert n_admit == float(np.sum(eng.metrics().arrivals_accepted))


def test_tracer_diag_materialized_once_per_chunk():
    """Regression (PR 9): ``_trace_part`` materializes the decision diag to
    numpy once per chunk before the record loop. Asserted structurally (the
    tracer receives numpy scalars, never device arrays — each device-array
    index is one device->host sync) and by timing (the chunk-level
    materialization is cheaper than per-record device reads)."""
    recorded = []

    class SpyTracer:
        def record(self, **fields):
            recorded.append(fields)

    width = 64
    cfg = SMALL._replace(max_arrivals=width)
    pol = make_policy(SECOND, rho=0.05, capacity=cfg.capacity)
    eng = OnlineAdmissionEngine(cfg, GRID, SECOND, pol, micro_batch=width,
                                tracer=SpyTracer())
    eng.tick(jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1), width)
    for k in keys:
        eng.submit(Arrival.draw(k, cfg))
    eng.flush()
    assert len(recorded) == width
    for rec in recorded:
        for field in ("score", "threshold", "fits"):
            assert not isinstance(rec[field], jax.Array), field

    diag = eng._last_diag
    assert diag is not None
    n_rep = 10
    t0 = time.perf_counter()
    for _ in range(n_rep):
        d = jax.tree.map(np.asarray, diag)    # what _trace_part does
        [float(d.score[j]) for j in range(width)]
    once_per_chunk = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n_rep):
        [float(diag.score[j]) for j in range(width)]   # the old per-record
    per_record = time.perf_counter() - t0              # device reads
    assert once_per_chunk < per_record, (once_per_chunk, per_record)


def test_snapshot_off_has_no_telemetry_key():
    cfg = SMALL._replace(telemetry=False, horizon_hours=2 * 24.0,
                         agg_refresh_steps=1)
    pol = make_policy(ZEROTH, threshold=cfg.capacity, capacity=cfg.capacity)
    eng = OnlineAdmissionEngine(cfg, GRID, ZEROTH, pol)
    eng.tick(jax.random.PRNGKey(0))
    snap = eng.metrics_snapshot()
    assert "telemetry" not in snap
    # and the renderer still produces valid engine-only exposition
    _check_prometheus_text(snapshot_to_prometheus(snap))


# ---------------------------------------------------------------------------
# Prometheus exposition + /metrics HTTP
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?P<labels>\{[^{}]*\})? '
    r'(?P<value>[-+]?(?:[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|Inf)|NaN)$')
_LABEL_RE = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"$')


def _check_prometheus_text(text):
    """Hand validator of the text exposition format (version 0.0.4): every
    line is # HELP / # TYPE or a well-formed sample; every sample belongs to
    a declared family; histogram buckets are cumulative with le=+Inf equal
    to _count. Returns {family: type}."""
    assert text.endswith("\n")
    families = {}
    hist_buckets = {}  # family -> list of (le, cum)
    for line in text.rstrip("\n").split("\n"):
        assert line == line.strip() and line, f"bad line {line!r}"
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            _, kind, name, rest = line.split(" ", 3)
            if kind == "TYPE":
                assert rest in ("counter", "gauge", "histogram"), line
                families[name] = rest
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line {line!r}"
        name, labels = m.group("name"), m.group("labels")
        if labels:
            for pair in labels[1:-1].split(","):
                assert _LABEL_RE.match(pair), f"bad label {pair!r} in {line!r}"
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in families:
                base = name[: -len(suffix)]
        assert base in families, f"sample {name!r} has no # TYPE"
        if families[base] == "histogram":
            assert base != name, \
                f"histogram family {base!r} has a bare sample"
        if name.endswith("_bucket"):
            le = dict(p.split("=", 1) for p in labels[1:-1].split(","))["le"]
            hist_buckets.setdefault(base, []).append(
                (float(le.strip('"').replace("+Inf", "inf")),
                 float(m.group("value"))))
        if name.endswith("_count") and base in hist_buckets:
            buckets = hist_buckets[base]
            cums = [c for _, c in buckets]
            assert cums == sorted(cums), f"{base}: non-cumulative buckets"
            assert buckets[-1][0] == float("inf")
            assert buckets[-1][1] == float(m.group("value"))
    assert families
    return families


def test_snapshot_prometheus_exposition_valid(engine_run):
    _, _, _, snap, _ = engine_run
    text = snapshot_to_prometheus(snap)
    fams = _check_prometheus_text(text)
    for want in ("repro_admission_requests_total",
                 "repro_admission_admitted_total",
                 "repro_admission_decision_latency_seconds",
                 "repro_admission_occupancy_window_count"):
        assert want in fams, want
    assert fams["repro_admission_decision_latency_seconds"] == "histogram"
    # counters agree with the snapshot they were rendered from
    n_req = snap["engine"]["n_requests"]
    assert f"repro_admission_requests_total {n_req}\n" in text


def test_render_prometheus_escaping_and_types():
    h = HostHistogram((0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = render_prometheus([
        Metric("t_counter", "counter", "a counter",
               [({"q": 'sa"y\nhi\\'}, 3.0)]),
        Metric("t_hist", "histogram", "a histogram", [({}, h)]),
    ])
    _check_prometheus_text(text)
    assert r't_counter{q="sa\"y\nhi\\"} 3' in text
    assert 't_hist_bucket{le="+Inf"} 3' in text
    assert "t_hist_count 3" in text
    with pytest.raises(ValueError):
        render_prometheus([Metric("x", "summary", "bad type", [({}, 1)])])


def test_metrics_server_serves_and_404s():
    srv = MetricsServer(lambda: render_prometheus(
        [Metric("t_up", "gauge", "up", [({}, 1)])]), port=0)
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            body = resp.read().decode()
        assert "t_up 1" in body
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10)
        assert err.value.code == 404
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# tracer + logger units
# ---------------------------------------------------------------------------

def test_decision_tracer_buffers_and_drains(tmp_path):
    path = tmp_path / "t.jsonl"
    with DecisionTracer(path, capacity=3) as tr:
        tr.record(step=0, score=jax.numpy.float32(1.5), verdict=True)
        tr.record(step=1, score=np.float64(2.25), verdict=False)
        assert tr.n_recorded == 2 and tr.n_written == 0  # still buffered
        tr.record(step=2, score=0.5, verdict=True)       # hits capacity
        assert tr.n_written == 3
        tr.record(step=3, arr=np.arange(2.0), verdict=True)
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["step"] for r in recs] == [0, 1, 2, 3]
    assert recs[0]["score"] == 1.5 and recs[1]["score"] == 2.25
    assert recs[3]["arr"] == [0.0, 1.0]
    assert all(isinstance(r["verdict"], bool) for r in recs)


def test_logger_rooted_and_level_controls(monkeypatch):
    assert get_logger("foo.bar").name == "repro.foo.bar"
    assert get_logger("repro.sim.importance").name == "repro.sim.importance"
    root = logging.getLogger("repro")
    old_level = root.level
    try:
        from repro.obs.log import set_level
        set_level("WARNING")
        assert not get_logger("x").isEnabledFor(logging.INFO)
        set_level("DEBUG")
        assert get_logger("x").isEnabledFor(logging.DEBUG)
        # env var configures the root on (re)initialization
        monkeypatch.setenv("REPRO_LOG_LEVEL", "INFO")
        monkeypatch.setattr(root, "_repro_obs_configured", False,
                            raising=False)
        assert get_logger("y").isEnabledFor(logging.INFO)
        assert not get_logger("y").isEnabledFor(logging.DEBUG)
        with pytest.raises(ValueError):
            set_level("NOT_A_LEVEL")
    finally:
        root.setLevel(old_level)
        root._repro_obs_configured = True


# ---------------------------------------------------------------------------
# daemon graceful shutdown (subprocess)
# ---------------------------------------------------------------------------

def test_daemon_sigterm_graceful_with_live_metrics():
    env = dict(os.environ, PYTHONPATH="src")  # main() enables the cache
    cmd = [sys.executable, "-m", "repro.launch.admission_daemon",
           "--capacity", "500", "--hours", "720", "--dt", "24",
           "--max-slots", "96", "--micro-batch", "4",
           "--arrival-rate", "0.08", "--param", "0.05",
           "--metrics-port", "0", "--throttle", "0.25"]
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    head, port = [], None
    try:
        for line in proc.stdout:  # closes on daemon exit, so no hang
            head.append(line)
            m = re.search(r"metrics: http://127\.0\.0\.1:(\d+)/metrics", line)
            if m:
                port = int(m.group(1))
                break
        assert port, "daemon never announced /metrics:\n" + "".join(head)
        body, deadline = "", time.time() + 120
        while time.time() < deadline:
            try:
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=5
                ).read().decode()
                if "repro_admission_ticks_total" in body:
                    break
            except (urllib.error.URLError, ConnectionError):
                pass
            time.sleep(0.25)
        _check_prometheus_text(body)
        assert "repro_admission_requests_total" in body
        assert "repro_admission_admitted_total" in body  # telemetry enabled
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    full = "".join(head) + out
    assert proc.returncode == 0, full
    assert "shutting down gracefully" in full
    assert "final snapshot" in full
    snap_line = full.rsplit("final snapshot ", 1)[1].splitlines()[0]
    snap = json.loads(snap_line)
    assert snap["engine"]["n_ticks"] >= 1
    assert "telemetry" in snap


# ---------------------------------------------------------------------------
# bca_ci fast path (satellite)
# ---------------------------------------------------------------------------

def test_bca_ci_vectorized_identical_to_loop():
    rng = np.random.default_rng(5)
    vals = rng.gamma(2.0, 1.0, size=60)
    w = rng.uniform(0.5, 2.0, size=60)

    def loop_stat(v, wt):  # not `is weighted_mean` -> general loop path
        return weighted_mean(v, wt)

    for weights in (None, w):
        fast = bca_ci(vals, weights, n_resamples=2_000, seed=3)
        slow = bca_ci(vals, weights, stat=loop_stat, n_resamples=2_000,
                      seed=3)
        assert fast == slow  # bit-identical CI, not approximately


def test_bca_ci_vectorized_is_faster():
    rng = np.random.default_rng(6)
    vals = rng.gamma(2.0, 1.0, size=200)

    def loop_stat(v, wt):
        return weighted_mean(v, wt)

    t0 = time.perf_counter()
    bca_ci(vals, n_resamples=10_000, seed=0)
    t_fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    bca_ci(vals, stat=loop_stat, n_resamples=10_000, seed=0)
    t_loop = time.perf_counter() - t0
    assert t_fast < t_loop, (t_fast, t_loop)
