"""AdmissionCore extraction is pure code motion.

``tests/data/golden_sim_metrics.npz`` (generated by
``tools/gen_sim_goldens.py``, which says how it depends on JAX's random
streams) pins every metric field of three reference runs; the refactored
``make_run``/``make_fleet_run`` — now thin scan drivers over
``sim.core.make_admission_core`` — must reproduce them **bit-for-bit**, not
approximately.
"""
import os

import jax
import numpy as np
import pytest

from repro.core import (AZURE_PRIORS, SECOND, ZEROTH, fleet_policy,
                        geometric_grid, make_policy)
from repro.sim import (FleetConfig, LeastUtilizedRouter, SimConfig,
                       make_admission_core, make_fleet_run, make_run)

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "golden_sim_metrics.npz")

# mirrors tools/gen_sim_goldens.py exactly — regenerate the fixture there
CFG = SimConfig(capacity=500.0, arrival_rate=0.08, horizon_hours=30 * 24.0,
                dt=24.0, max_slots=96, max_arrivals=4, d_points=8,
                priors=AZURE_PRIORS)
GRID = geometric_grid(24.0, 3 * 30 * 24.0, 12)
FLEET2 = FleetConfig(base=CFG, capacities=(300.0, 200.0))


def _flat(prefix, metrics):
    out = {}
    for name, val in metrics._asdict().items():
        if hasattr(val, "_asdict"):
            out.update(_flat(f"{prefix}/{name}", val))
        else:
            out[f"{prefix}/{name}"] = np.asarray(val)
    return out


@pytest.fixture(scope="module")
def rebuilt():
    arrays = {}
    arrays.update(_flat("single/zeroth", make_run(CFG, GRID, ZEROTH)(
        jax.random.PRNGKey(0),
        make_policy(ZEROTH, threshold=300.0, capacity=CFG.capacity))))
    arrays.update(_flat("single/second_k3", make_run(
        CFG._replace(agg_refresh_steps=3), GRID, SECOND)(
            jax.random.PRNGKey(1),
            make_policy(SECOND, rho=0.05, capacity=CFG.capacity))))
    arrays.update(_flat("fleet2/second", make_fleet_run(
        FLEET2, GRID, SECOND, router=LeastUtilizedRouter())(
            jax.random.PRNGKey(2),
            fleet_policy(SECOND, capacities=FLEET2.capacities, rho=0.05))))
    return arrays


def test_golden_metrics_bit_for_bit(rebuilt):
    gold = np.load(GOLDEN)
    assert len(gold.files) >= 40
    for name in gold.files:
        np.testing.assert_array_equal(gold[name], rebuilt[name], err_msg=name)


def test_record_decisions_consistent_with_metrics():
    cfg = CFG._replace(agg_refresh_steps=3)
    pol = make_policy(SECOND, rho=0.05, capacity=cfg.capacity)
    key = jax.random.PRNGKey(1)
    metrics, accept = make_run(cfg, GRID, SECOND,
                               record_decisions=True)(key, pol)
    assert accept.shape == (cfg.n_steps, cfg.max_arrivals)
    assert float(np.sum(accept)) == float(metrics.arrivals_accepted)
    # recording must not perturb the run itself
    plain = make_run(cfg, GRID, SECOND)(key, pol)
    for name, val in plain._asdict().items():
        np.testing.assert_array_equal(np.asarray(val),
                                      np.asarray(getattr(metrics, name)),
                                      err_msg=name)


def test_fleet_record_decisions_shapes():
    pol = fleet_policy(SECOND, capacities=FLEET2.capacities, rho=0.05)
    metrics, accept, assign = make_fleet_run(
        FLEET2, GRID, SECOND, record_decisions=True)(
            jax.random.PRNGKey(2), pol)
    T, A = CFG.n_steps, CFG.max_arrivals
    assert accept.shape == (T, FLEET2.n_clusters, A)
    assert assign.shape == (T, A)
    assert float(np.sum(accept)) == float(np.sum(
        metrics.per_cluster.arrivals_accepted))


def test_core_init_and_refresh_idempotent():
    core = make_admission_core(CFG, GRID, SECOND)
    cs = core.init()
    assert cs.slots.alive.shape == (CFG.max_slots,)
    assert cs.agg_el.shape == cs.agg_vl.shape == (GRID.shape[0],)
    once = core.refresh_aggregates(cs)
    twice = core.refresh_aggregates(once)
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(twice)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
