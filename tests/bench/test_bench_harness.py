"""The benchmark harness on the CPU at a small size: cells, configurations,
traffic and metrics found by name, the open-loop timing, the peaks table and
the work count, and the refusal to measure without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

import bench_checkout as bc

NEW_METRIC = {"name": "requests_seen.test", "unit": "count", "better": "higher",
              "source": "program_counter", "layer": "front-end",
              "moves": "decision_p50_ms", "workloads": [bc.CELL]}
NEW_READER = '''"""Flush parts decided in the window (a metric added as a file)."""


def read(layer):
    return float(layer.batch_parts)
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = bc.make_checkout(str(tmp_path_factory.mktemp("checkout")),
                            extra_per_layer=[NEW_METRIC])
    with open(os.path.join(root, "bench", "metrics",
                           NEW_METRIC["name"] + ".py"), "w") as f:
        f.write(NEW_READER)
    return root


@pytest.fixture(scope="module")
def traced(checkout):
    return bc.run_small(checkout, seed=3_000_000_019, trace=1)


def test_new_config_traffic_and_metric_are_found_by_name(checkout, traced):
    import run

    spec = run.benchmark(checkout)
    cell = run.cell(spec, bc.CELL, checkout)
    assert cell.config["max_slots"] == bc.SMALL["max_slots"]
    assert cell.traffic == bc.TRAFFIC
    code, result = traced
    assert code == 0 and result["correct"]
    # the reader added as a file reports; the TPU-trace readers find no
    # TPU in a CPU trace and leave their metrics out
    assert result["metrics"][NEW_METRIC["name"]]["value"] > 0
    assert "batch_fill.serve" in result["metrics"]
    assert "device_idle.serve" not in result["metrics"]
    assert list(result)[-1] == "checks"
    with pytest.raises(KeyError):
        run.cell(spec, "no_such_cell", checkout)


def test_end_to_end_metrics_of_a_run(checkout):
    code, result = bc.run_small(checkout, seed=7)
    assert code == 0 and result["correct"], result["checks"]
    m = result["metrics"]
    assert set(m) == {"decision_p50_ms", "decisions_per_s", "setup_s"}
    assert m["decision_p50_ms"]["value"] > 0
    assert m["decisions_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"


class _StallingEngine:
    """Answers every request at once on its own thread, except while a tick
    holds the lock: the first tick of the window stalls for ``stall_s``."""

    def __init__(self, stall_s: float):
        self.stall_s, self.ticks, self.decisions = stall_s, 0, 0
        self.lock = threading.Lock()

    def tick(self, events=None):
        with self.lock:
            if self.ticks == 0:
                time.sleep(self.stall_s)
            self.ticks += 1

    def submit(self, arrival) -> Future:
        fut = Future()

        def answer():
            with self.lock:
                self.decisions += 1
                fut.set_result(True)

        threading.Thread(target=answer).start()
        return fut


def _p99_with_stall(stall_s: float) -> float:
    from drivers import served

    n = 200
    sched = SimpleNamespace(windows_per_s=4.0, n_fill=0, n_windows=4,
                            first=np.array([0, 50, 100, 150, 200]),
                            due_s=np.linspace(0.0, 0.99, n))
    eng = _StallingEngine(stall_s)
    rec = served.Recorder(eng, n)
    t0 = time.perf_counter() + 0.05
    sub = served._serve_window(eng, sched, [None] * n, lambda: None, rec,
                               t0, 1.0)
    rec.wait(5.0)
    lat, late = served.latencies(rec, sched, np.asarray(sub), t0)
    assert len(lat) == n and np.all(lat >= 0)
    return float(np.percentile(lat, 99))


def test_open_loop_times_each_request_from_its_due_time():
    quiet = _p99_with_stall(0.0)
    stalled = _p99_with_stall(0.3)
    # the stall delays every request due while it lasts, and each is timed
    # from when it was due, so the tail carries the stall
    assert quiet < 100.0
    assert stalled > 200.0


def test_work_count_is_the_same_for_every_aggregate_lane():
    import workcount

    config = bc.small_config()
    counts = {lane: workcount.for_config(dict(config, agg_backend=lane))
              for lane in ("fused", "kernel", "reference")}
    assert len(set(counts.values())) == 1
    ops, nbytes = counts["fused"]
    assert ops > 0 and nbytes > 0


def test_peaks_table():
    import peaks

    p = peaks.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    t, bound = peaks.least_time(1e9, 1e3, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(1e9 / 197e12)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.least_time(1.0, 1.0, "cpu")


def _bench_cmd(cwd: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cluster_serve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_to_measure_without_a_tpu():
    proc = _bench_cmd(bc.REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(bc.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bc.BENCH, tmp_path / "bench")
    proc = _bench_cmd(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_names_and_cells():
    with open(os.path.join(bc.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    import run

    for w in spec["workloads"]:
        c = run.cell(spec, w["name"])
        assert c.traffic["driver"] == "served"
        assert os.path.exists(os.path.join(bc.BENCH, "drivers",
                                           c.traffic["driver"] + ".py"))
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(bc.BENCH, "metrics",
                                           m["name"] + ".py"))
