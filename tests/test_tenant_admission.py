"""Tenant-history admission at a small size on the CPU.

A configuration with ``n_tenants`` keeps a device tenant table in its
``CoreState``: each tick folds every slot's window observations into its
tenant's row, each decide forms its requests' beliefs from their rows and
folds the placed requests' sizes. These tests hold it to the plain float64
reference (``bench/reference_tenant.py``), the offline scan, conservation
of every row, and the Gamma ratio every belief runs on to SciPy; and they
check that configurations without tenants keep their packed part and state.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.special import gammaln

from repro.core import AZURE_PRIORS, SECOND, geometric_grid, make_policy
from repro.core.belief import TENANT_COLUMNS, log_gamma_ratio
from repro.obs.export import snapshot_to_prometheus
from repro.serve import ExternalEvents, OnlineAdmissionEngine
from repro.serve.admission import PART_ROWS, TENANT_PART_ROWS
from repro.sim import (FleetConfig, SimConfig, draw_arrival_stream,
                       make_config, make_fleet_config, make_run)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: 64 slots, 16 tenants, 40 windows of 6 h; 3 requests a window fill the
#: 200 cores within the run, so the policy admits and rejects
CFG = SimConfig(capacity=200.0, arrival_rate=0.5, horizon_hours=40 * 6.0,
                dt=6.0, max_slots=64, max_arrivals=8, d_points=8,
                priors=AZURE_PRIORS, n_tenants=16)
GRID = geometric_grid(6.0, 3 * 240.0, 12)
RHO = 0.05


def _offline_inputs(key, cfg):
    k_stream, k_scan = jax.random.split(key)
    return (draw_arrival_stream(k_stream, cfg),
            jax.random.split(k_scan, cfg.n_steps))


def test_served_matches_offline_bit_for_bit():
    """Every tick (dynamics and tenant fold) and every decide (lookup,
    placement, arrival fold) against ``make_run`` scanning the same draw."""
    pol = make_policy(SECOND, rho=RHO, capacity=CFG.capacity)
    key = jax.random.PRNGKey(4)
    m_off, acc_off = make_run(CFG, GRID, SECOND,
                              record_decisions=True)(key, pol)
    stream, keys = _offline_inputs(key, CFG)
    assert stream.tenant.shape == (CFG.n_steps, CFG.max_arrivals)
    eng = OnlineAdmissionEngine(CFG, GRID, SECOND, pol)
    n_arr = np.asarray(stream.n_arrivals)
    acc_on = []
    for t in range(CFG.n_steps):
        eng.tick(keys[t])
        acc_on.append(eng.decide_slice(
            jax.tree.map(lambda x: x[t], stream),
            np.arange(CFG.max_arrivals) < n_arr[t]))
    np.testing.assert_array_equal(np.stack(acc_on), np.asarray(acc_off))
    acc_off = np.asarray(acc_off)
    assert acc_off.any() and (~acc_off & (np.arange(8) < n_arr[:, None])).any()
    m_on = eng.metrics()
    for name, val in m_off._asdict().items():
        np.testing.assert_array_equal(np.asarray(val),
                                      np.asarray(getattr(m_on, name)), name)


def _reference_config() -> dict:
    pr = AZURE_PRIORS
    return {
        "capacities": [CFG.capacity], "max_slots": CFG.max_slots,
        "arrival_rate_per_h": CFG.arrival_rate, "dt_h": CFG.dt,
        "max_arrivals": CFG.max_arrivals,
        "priors": {k: float(getattr(pr, k)) for k in pr._fields},
        "policy": {"kind": "second", "rho": RHO},
        "agg_refresh_steps": 1,
        "grid": {"t_min_h": 6.0, "t_max_h": 720.0, "points": 12,
                 "d_points": CFG.d_points},
        "tenants": {"count": CFG.n_tenants, "zipf_s": 1.0},
    }


@pytest.fixture(scope="module")
def observed_run():
    """The engine served 40 windows of observed events from
    ``generator.World`` and requests drawn as the tenant cell draws them,
    one flush part a window."""
    import generator
    from drivers import served_tenant

    config = _reference_config()
    counts = np.minimum(np.random.default_rng(11).poisson(3.0, CFG.n_steps),
                        CFG.max_arrivals)
    first = np.concatenate([[0], np.cumsum(counts)])
    host = served_tenant.draw_arrivals(config, int(first[-1]), seed=5)
    world = generator.World(config, host, seed=5)
    log = generator.DecisionLog(int(first[-1]))
    pol = make_policy(SECOND, rho=RHO, capacity=CFG.capacity)
    eng = OnlineAdmissionEngine(CFG, GRID, SECOND, pol)
    width = CFG.max_arrivals
    for w in range(CFG.n_steps):
        ev = world.next_events(log)
        eng.tick(events=ExternalEvents(**{k: v[0] for k, v in ev.items()}))
        rows = np.arange(first[w], first[w + 1])
        if not len(rows):
            continue
        served_tenant._decide_batch(eng, host, rows, width, log)
    return config, host, world, log, eng, first


def test_engine_matches_float64_reference(observed_run):
    """Zero exact mismatches (every window's used cores and failed
    scale-outs, admitted and rejected counts); every verdict equal where the
    reference's score lies more than 1e-4 rho from rho; and the tenant
    table equal to the float64 table to float32 rounding (its entries are
    whole numbers here, so exactly)."""
    import generator
    import reference_tenant

    config, host, world, log, eng, first = observed_run
    rep = reference_tenant.TenantReplay(config)
    arr = {k: np.asarray(v, np.float64) for k, v in host.items()}
    scored = 0
    for w in range(CFG.n_steps):
        rep.tick(generator.dense(world.issued[w], (1, CFG.max_slots)),
                 aggregate=True)
        rows = list(range(first[w], first[w + 1]))
        if not rows:
            continue
        served = log.admit[rows]
        verdicts = rep.flush([{"c0": arr["c0"][i], "tenant": arr["tenant"][i]}
                              for i in rows], served=served)
        for v, ok in zip(verdicts, served):
            if abs(v.score - RHO) > 1e-4 * RHO:
                assert v.admit == bool(ok), (w, v)
                scored += 1
    rep.finish()
    m = eng.metrics()
    np.testing.assert_array_equal(np.asarray(m.util_trace, np.float64),
                                  np.stack(rep.util_trace)[:, 0])
    np.testing.assert_array_equal(np.asarray(m.fail_trace, np.float64),
                                  np.stack(rep.fail_trace)[:, 0])
    assert float(m.arrivals_accepted) == rep.accepted[0]
    assert float(m.arrivals_rejected) == rep.rejected[0]
    assert 0 < rep.accepted[0] and 0 < rep.rejected[0]
    assert scored > 50
    table = eng.tenant_table()
    assert table.shape == (CFG.n_tenants, len(TENANT_COLUMNS))
    np.testing.assert_allclose(table, rep.table, rtol=2 ** -23, atol=0.0)
    assert (rep.table[:, 0] > 0).sum() > 4


def test_snapshot_counts_lookups_and_informed_rows(observed_run):
    _, _, _, log, eng, first = observed_run
    snap = eng.metrics_snapshot()
    eng_snap = snap["engine"]
    lookups = eng_snap["tenant_lookups_total"]
    assert lookups["informed"] + lookups["cold"] == first[-1]
    assert lookups["informed"] > 0 and lookups["cold"] > 0
    table = eng.tenant_table()
    assert eng_snap["tenant_rows_informed"] == int(np.sum(table[:, 5] > 0))
    # three device calls a decide part, with the lookup and the fold inside
    calls = eng_snap["device_calls_total"]["part"]
    assert calls["sum"] == 3 * calls["count"]
    text = snapshot_to_prometheus(snap)
    assert 'repro_admission_tenant_lookups_total{outcome="informed"}' in text
    assert "repro_admission_tenant_rows_informed " in text


def test_every_row_is_the_sum_of_its_slots_increments():
    """Conservation over one window at a time: each row grows by exactly
    the window's observations of the slots of its tenant (deaths clamped to
    cores, core-hours, alive hours, scale-outs and their cores beyond one
    each), with random events on a table of random tenants."""
    from repro.sim.core import make_admission_core

    cfg = CFG._replace(max_slots=32, n_tenants=5)
    core = make_admission_core(cfg, GRID, SECOND)
    rng = np.random.default_rng(3)
    s = cfg.max_slots
    cs = core.init()
    alive = rng.random(s) < 0.7
    cores = np.where(alive, rng.integers(1, 9, s), 0).astype(np.float32)
    tenant = rng.integers(0, cfg.n_tenants, s).astype(np.int32)
    cs = cs._replace(slots=cs.slots._replace(
        alive=jnp.asarray(alive), cores=jnp.asarray(cores),
        tenant=jnp.asarray(tenant)))
    step = jax.jit(lambda cs, ev: core.apply_observed(cs, ev)[0])
    for _ in range(6):
        ev = ExternalEvents(
            core_deaths=rng.integers(0, 4, s).astype(np.float32),
            spont_death=rng.random(s) < 0.1,
            scaleout_cores=rng.integers(0, 6, s).astype(np.float32),
            n_scaleouts=rng.integers(0, 3, s).astype(np.float32))
        before = np.asarray(cs.tenants, np.float64).sum(axis=0)
        held, c = np.asarray(cs.slots.alive), np.asarray(cs.slots.cores)
        deaths = np.minimum(ev.core_deaths, c) * held
        left = np.where(ev.spont_death & held, 0.0, c - deaths)
        still = held & (left > 0)
        inc = np.stack([deaths, c * cfg.dt * held, cfg.dt * still,
                        ev.n_scaleouts * still,
                        (ev.scaleout_cores - ev.n_scaleouts) * still,
                        ev.n_scaleouts * still], axis=-1)
        want = before.copy()
        np.add.at(want, np.asarray(cs.slots.tenant), inc)
        cs = step(cs, ev)
        np.testing.assert_array_equal(
            np.asarray(cs.tenants, np.float64).sum(axis=0), want)


@pytest.mark.parametrize("start", [0.0, 2.0 ** 24 - 5.0, 2.0 ** 27 + 3.0,
                                   2.0 ** 40 + 7.0])
def test_tenant_sums_keep_counting_past_float32s_integer_range(start):
    """A head tenant's exposure grows by thousands of core-hours a window
    and passes 2**24 within a year of uptime; past 2**27 a plain float32
    sum drops a slot's 6-12 core-hours. The compensated table holds every
    column exact against a float64 sum, from empty up to 2**40."""
    from repro.core.belief import fold_tenant_events, init_tenant_table

    rng = np.random.default_rng(5)
    n_steps, s, t = 500, 16, 3
    tenant = jnp.asarray(rng.integers(0, t, s), jnp.int32)
    inc = rng.integers(0, 3, (n_steps, s)).astype(np.float32) * 6.0
    table = init_tenant_table(t).at[0].set(np.float32(start))

    def step(table, x):
        return fold_tenant_events(
            table, tenant, core_deaths=x, exposure_core_hours=x,
            n_scaleouts=x, scaleout_cores=2.0 * x, alive_hours=x), None

    got, _ = jax.jit(lambda tb: jax.lax.scan(step, tb, jnp.asarray(inc)))(
        table)
    want = np.zeros((t, len(TENANT_COLUMNS))) + np.float64(np.float32(start))
    np.add.at(want, np.asarray(tenant), inc.astype(np.float64).sum(0)[:, None])
    np.testing.assert_array_equal(
        np.asarray(got, np.float64).sum(axis=0), want)
    if start >= 2.0 ** 27:
        plain = np.full(t, np.float32(start))
        for x in inc:
            plain = plain + np.bincount(np.asarray(tenant), x,
                                        t).astype(np.float32)
        assert np.any(plain.astype(np.float64) != want[:, 0])


NU = AZURE_PRIORS.nu


@pytest.mark.parametrize("p", [NU, 2 * NU - 1, 1.0, 2.0],
                         ids=["nu", "z+1", "1", "2"])
def test_gamma_ratio_matches_scipy_from_prior_shapes_to_1e7(p):
    """log Gamma(a+p)/Gamma(a) in float32 against SciPy in float64, for a
    from 0.05 to 1e7: within 4 float32 ulps of the larger of the ratio's
    own size and 4 (the size of the shift terms below a = 4). A difference
    of two float32 ``gammaln`` is off by more than the ratio at a = 1e5."""
    a = np.geomspace(0.05, 1e7, 3000).astype(np.float32)
    p32 = np.float32(p)
    got = np.asarray(jax.jit(log_gamma_ratio)(jnp.asarray(a), p32),
                     np.float64)
    a64 = a.astype(np.float64)
    want = gammaln(a64 + float(p32)) - gammaln(a64)
    ulp = np.spacing(np.maximum(np.abs(want), 4.0).astype(np.float32))
    assert np.all(np.abs(got - want) <= 4 * ulp.astype(np.float64))
    old = np.asarray(jax.scipy.special.gammaln(jnp.float32(1e5) + p32)
                     - jax.scipy.special.gammaln(jnp.float32(1e5)))
    assert abs(float(old) - (gammaln(1e5 + float(p32)) - gammaln(1e5))) > 1e-3


def test_configurations_without_tenants_keep_their_part_and_state():
    """No tenant table, slot tenant ids or lookup counters (empty pytree
    nodes, so the same programs as before), the packed part ``[17, 8]``;
    a tenant configuration's part carries the tenant in place of the
    beliefs, ``[6, 8]``."""
    plain = CFG._replace(n_tenants=0)
    pol = make_policy(SECOND, rho=RHO, capacity=plain.capacity)
    eng = OnlineAdmissionEngine(plain, GRID, SECOND, pol)
    assert eng._pad_part.shape == (PART_ROWS, 8) == (17, 8)
    assert eng._cs.tenants is None and eng._cs.slots.tenant is None
    assert eng._win.lookups is None
    assert eng.tenant_table() is None
    assert "tenant_lookups_total" not in eng.metrics_snapshot()["engine"]
    ten = OnlineAdmissionEngine(CFG, GRID, SECOND, pol)
    assert ten._pad_part.shape == (TENANT_PART_ROWS, 8) == (6, 8)


def test_tenants_refuse_shards_fleets_and_other_information_models():
    pol = make_policy(SECOND, rho=RHO, capacity=CFG.capacity)
    with pytest.raises(ValueError, match="out of scope"):
        OnlineAdmissionEngine(CFG, GRID, SECOND, pol, shards=2)
    with pytest.raises(ValueError, match="out of scope"):
        OnlineAdmissionEngine(FleetConfig(base=CFG, capacities=(300.0, 300.0)),
                              GRID, SECOND, pol)
    with pytest.raises(ValueError, match="one cluster"):
        make_fleet_config((300.0, 300.0), n_tenants=4)
    with pytest.raises(ValueError, match="GLOBAL"):
        make_config(n_tenants=4, prior_mode="pseudo", n_pseudo_obs=5)
