"""The trace reduction on a small trace recorded on a TPU v5e chip
(``bench/data/small_trace``): two bursts of one jitted program with a 60 ms
host stall between them, which the idle share must show."""
from __future__ import annotations

import json
import os

import pytest

import bench_checkout as bc

DATA = os.path.join(bc.BENCH, "data", "small_trace")


@pytest.fixture(scope="module")
def summary():
    import trace_reduce

    return trace_reduce.reduce_dir(DATA)


@pytest.fixture(scope="module")
def meta():
    with open(os.path.join(DATA, "README.json")) as f:
        return json.load(f)


def test_device_busy_and_the_stall(summary, meta):
    assert summary["n_devices"] == 1
    window, stall = meta["window_s"], meta["stall_s"]
    assert 0.0 < summary["busy_s"] < window - stall
    idle = 1.0 - summary["busy_s"] / window
    assert idle >= stall / window
    # the longest idle gap is the host's sleep
    cause, seconds = summary["idle_gaps"][0]
    assert "sleep" in cause
    assert seconds == pytest.approx(stall, rel=0.1)


def test_program_time_by_module_name(summary):
    import trace_reduce

    n, seconds = trace_reduce.module_stats(summary, ["jit__lambda"])
    assert n == 40
    # a module's span also covers the gaps between its operations
    assert 0.0 < seconds <= 1.05 * summary["busy_s"]
    assert trace_reduce.module_stats(summary, ["jit_absent"]) == (0, 0.0)
    assert trace_reduce.module_name("jit_fleet_decide(1234)") == \
        "jit_fleet_decide"
    assert summary["device_ops"] and len(summary["device_ops"]) <= 10
