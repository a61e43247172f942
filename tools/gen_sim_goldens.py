"""Regenerate tests/data/golden_sim_metrics.npz — the bit-for-bit anchor for
the AdmissionCore extraction.

The tests assert that ``make_run``/``make_fleet_run`` reproduce these
metrics exactly. They depend on JAX's random streams: the file is generated
under the ``jax_threefry_partitionable=True`` default of the pinned JAX
(metrics drawn under the older non-partitionable streams differ, and are
reproduced only with ``JAX_THREEFRY_PARTITIONABLE=0``). Regenerate ONLY when
a deliberate semantic change to the simulator or its random streams lands
(and say so in the change):

  PYTHONPATH=src python tools/gen_sim_goldens.py
"""
import os

import numpy as np

import jax

from repro.core import (AZURE_PRIORS, SECOND, ZEROTH, fleet_policy,
                        geometric_grid, make_policy)
from repro.sim import (FleetConfig, LeastUtilizedRouter, SimConfig,
                       make_fleet_run, make_run)

OUT = os.path.join(os.path.dirname(__file__), "..", "tests", "data",
                   "golden_sim_metrics.npz")

CFG = SimConfig(capacity=500.0, arrival_rate=0.08, horizon_hours=30 * 24.0,
                dt=24.0, max_slots=96, max_arrivals=4, d_points=8,
                priors=AZURE_PRIORS)
GRID = geometric_grid(24.0, 3 * 30 * 24.0, 12)
CFG_K3 = CFG._replace(agg_refresh_steps=3)
FLEET2 = FleetConfig(base=CFG, capacities=(300.0, 200.0))


def flat(prefix: str, metrics) -> dict:
    out = {}
    for name, val in metrics._asdict().items():
        if hasattr(val, "_asdict"):  # FleetMetrics.per_cluster
            out.update(flat(f"{prefix}/{name}", val))
        else:
            out[f"{prefix}/{name}"] = np.asarray(val)
    return out


def main():
    arrays = {}

    run_z = make_run(CFG, GRID, ZEROTH)
    pol_z = make_policy(ZEROTH, threshold=300.0, capacity=CFG.capacity)
    arrays.update(flat("single/zeroth",
                       run_z(jax.random.PRNGKey(0), pol_z)))

    run_s = make_run(CFG_K3, GRID, SECOND)
    pol_s = make_policy(SECOND, rho=0.05, capacity=CFG.capacity)
    arrays.update(flat("single/second_k3",
                       run_s(jax.random.PRNGKey(1), pol_s)))

    frun = make_fleet_run(FLEET2, GRID, SECOND, router=LeastUtilizedRouter())
    fpol = fleet_policy(SECOND, capacities=FLEET2.capacities, rho=0.05)
    arrays.update(flat("fleet2/second",
                       frun(jax.random.PRNGKey(2), fpol)))

    np.savez(os.path.abspath(OUT), **arrays)
    print(f"wrote {os.path.abspath(OUT)} ({len(arrays)} arrays)")


if __name__ == "__main__":
    main()
