"""Shared test configuration.

Enables jax's persistent compilation cache (``repro.compile_cache``:
``$JAX_COMPILATION_CACHE_DIR`` or the gitignored ``<repo>/.jax_cache``): the
suite is compile-dominated on CPU, so warm reruns — the common local dev
loop — skip most XLA work. Cold CI runs are unaffected.

Also hosts two tier-1 runtime guards:

  * ``sim_cache`` — a session-scoped compiled-simulator cache. The tuning
    tests (property, oracle, invariance) all drive the same small config;
    building ``make_run`` once per policy kind for the whole session keeps
    the suite's XLA compile count flat as calibration tests accumulate.
  * a session-scoped time budget (``tests/time_budget.json``): in CI, the
    default (non-slow) suite must finish inside the recorded budget, so
    compile-heavy tests cannot creep the tier-1 wall time unnoticed.
"""
import json
import os
import time

import jax
import pytest

from repro.compile_cache import enable_compile_cache

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

_BUDGET_FILE = os.path.join(os.path.dirname(__file__), "time_budget.json")


class SimCache:
    """Compiled-simulator cache: one small config + grid + key batch, with
    ``run(kind)`` building (and memoizing) the jitted simulator per policy
    kind and ``curve(kind, thetas)`` memoizing whole evaluated theta grids
    so property tests can share measurements."""

    def __init__(self):
        from repro.core import geometric_grid
        from repro.sim import make_config

        # small on purpose (mirrors test_sim.CFG): invariant checks, not
        # statistics; 30 steps / 96 slots / 12 grid points keep each
        # make_run compile a few seconds on CPU
        self.cfg = make_config(capacity=500.0, arrival_rate=0.08,
                               horizon_hours=30 * 24.0, dt=24.0,
                               max_slots=96, max_arrivals=4, d_points=8)
        self.grid = geometric_grid(24.0, 3 * 30 * 24.0, 12)
        self.keys = jax.random.split(jax.random.PRNGKey(7), 6)
        self.tau = 5e-3
        self._runs = {}
        self._curves = {}

    def run(self, kind: int):
        if kind not in self._runs:
            from repro.sim import make_run

            self._runs[kind] = make_run(self.cfg, self.grid, kind)
        return self._runs[kind]

    def curve(self, kind: int, thetas):
        """(agg_fail [T], util [T, R]) at ``thetas``, memoized."""
        import numpy as np

        key = (kind, tuple(float(t) for t in thetas))
        if key not in self._curves:
            from repro.tuning import eval_theta_grid

            m = eval_theta_grid(self.run(kind), kind, list(thetas), self.keys,
                                capacity=self.cfg.capacity)
            fails = np.asarray(m.failed_requests)
            reqs = np.asarray(m.total_requests)
            agg = fails.sum(1) / np.maximum(reqs.sum(1), 1.0)
            self._curves[key] = (agg, np.asarray(m.utilization))
        return self._curves[key]


@pytest.fixture(scope="session")
def sim_cache():
    return SimCache()


@pytest.fixture(scope="session", autouse=True)
def _tier1_time_budget(request):
    """CI-only guard: the default non-slow suite must finish within the
    budget recorded in tests/time_budget.json (generous — it catches
    order-of-magnitude creep, not noise). Local runs and explicit slow/-k
    selections are exempt."""
    t0 = time.time()
    yield
    if not os.environ.get("CI"):
        return
    opts = request.config.option
    if opts.markexpr != "not slow" or opts.keyword:
        return
    with open(_BUDGET_FILE, encoding="utf-8") as f:
        budget = json.load(f)["non_slow_seconds"]
    elapsed = time.time() - t0
    if elapsed > budget:
        raise RuntimeError(
            f"tier-1 (non-slow) suite took {elapsed:.0f}s, over the "
            f"{budget}s budget in {os.path.relpath(_BUDGET_FILE)}; either a "
            "test got much slower or the budget needs a deliberate bump")
