"""The tenant cell's driver, reference, control and fault at a small size on
the CPU: a sound traced run of ``drivers/served_tenant.py`` compares exactly
and reports ``tenant_informed.serve``; the tenant reference in the program's
place compares exactly in float64 and not in bfloat16
(``bench/control_tenant.py``); the planted ``tenant_fold_dropped`` and
``tenant_lookup_shifted`` (``bench/faults_tenant.py``) come out not
correct."""
from __future__ import annotations

import json
import os

import pytest

import bench_checkout as bc

#: paper_cluster_tenants cut to 2,500 cores, 256 slots, 64 tenants and an
#: 8-point grid; 80 fill windows give the tenants a history and bring the
#: cluster to where the policy rejects
SMALL = dict(capacities=[2500.0], max_slots=256, arrival_rate_per_h=4.0,
             max_arrivals=16, horizon_h=2160.0,
             grid=dict(t_min_h=6.0, t_max_h=2160.0, points=8, d_points=4),
             tenants=dict(count=64, zipf_s=1.0))
TRAFFIC = dict(driver="served_tenant", windows_per_s=20.0, fill_windows=80,
               counts_seed=0, compared_windows=60)
CELL = "small_tenant_serve"


def make_tenant_checkout(root: str, small=SMALL, traffic=TRAFFIC) -> str:
    """``bench_checkout.make_checkout`` with its one cell the small tenant
    cell."""
    root = bc.make_checkout(root)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "paper_cluster_tenants.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    config.update(small, name="small_tenants")
    with open(os.path.join(bench, "configs", "small_tenants.json"), "w",
              encoding="utf-8") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "small_tenant_steady.json"),
              "w", encoding="utf-8") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "small_tenants", "source": "cut",
                        "file": "bench/configs/small_tenants.json",
                        "reduced": [], "why": "CPU tests"}]
    spec["workloads"] = [{"name": CELL, "config": "small_tenants",
                          "traffic": "small_tenant_steady", "chips": 1,
                          "why": "CPU tests"}]
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            if "workloads" in m:
                m["workloads"] = [CELL]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_tenant_checkout(str(tmp_path_factory.mktemp("checkout")))


def _run(root, seed, trace=0, engine_hook=None):
    import run

    args = run.parse(["--workload", CELL, "--seed", str(seed), "--seconds",
                      "2.0", "--trace", str(trace)])
    with bc.jax_settings_kept():
        return run.run_cell(args, root=root, require_chip=False,
                            engine_hook=engine_hook)


def test_sound_tenant_run_is_correct_and_reports_informed_share(checkout):
    code, result = _run(checkout, seed=3_000_000_021, trace=1)
    assert code == 0 and result["correct"], result["checks"]
    assert result["checks"]["exact_mismatches"]["value"] == 0
    share = result["metrics"]["tenant_informed.serve"]["value"]
    assert 0.0 < share <= 100.0


@pytest.mark.parametrize("seed", [21, 22])
def test_tenant_fold_dropped_is_not_correct(checkout, seed):
    import faults_tenant

    code, result = _run(checkout, seed=seed,
                        engine_hook=faults_tenant.tenant_fold_dropped)
    assert code == 0
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("seed", [23, 24])
def test_tenant_lookup_shifted_is_caught_by_the_decisions_alone(checkout,
                                                                seed):
    """Each request decided on the next tenant's row: the table stays
    exact, so only the decisions' gap to the reference shows the fault."""
    import faults_tenant

    code, result = _run(checkout, seed=seed,
                        engine_hook=faults_tenant.tenant_lookup_shifted)
    assert code == 0
    assert not result["correct"], result["checks"]
    checks = result["checks"]
    assert checks["tenant_table_error"]["value"] == 0.0
    assert checks["decision_gap"]["value"] > checks["decision_gap"]["limit"]


def _control(checkout, seed, dtype):
    import control_tenant
    import run

    c = run.cell(run.benchmark(checkout), CELL, checkout)
    with bc.jax_settings_kept():
        return control_tenant.control_run(c.config, c.traffic, seed, 2.0,
                                          dtype)


def test_tenant_reference_in_its_own_place_compares_exactly(checkout):
    out = _control(checkout, 4, "float64")
    assert out["correct"], out["checks"]
    assert out["checks"]["decision_gap"]["value"] == 0.0
    assert out["checks"]["tenant_table_error"]["value"] == 0.0


def test_tenant_control_in_bfloat16_is_not_correct(checkout):
    out = _control(checkout, 1, "bfloat16")
    assert not out["correct"], out["checks"]
    assert out["checks"]["exact_mismatches"]["value"] == 0


def test_driver_refuses_a_zipf_exponent_the_program_does_not_draw():
    """The served draw and the offline scan's must share one exponent."""
    import run

    driver = run.load_module(os.path.join(bc.BENCH, "drivers",
                                          "served_tenant.py"))
    with open(os.path.join(bc.BENCH, "configs",
                           "paper_cluster_tenants.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    driver.check_config(config)
    config["tenants"] = dict(config["tenants"], zipf_s=1.2)
    with pytest.raises(ValueError, match="TENANT_ZIPF"):
        driver.check_config(config)
