"""Served tenant-history admission: ``served.py``'s run, every request naming
a tenant.

A run is ``drivers/served.py``'s (set-up, window, check), made on a private
copy of that module in which these names differ, and nothing else:

* ``generator.draw_arrivals``: each request also names a tenant, drawn
  with Zipf popularity (``tenants.zipf_s``, which ``check_config`` holds
  to the exponent of the program's own draw) over ``tenants.count``, and has
  its tenant's scaling type ``(lam, mu, sig)``, drawn once per tenant from
  the priors by the run's seed; its cores are ``1 + Poisson(sig)``;
* ``build_engine``: the configuration's one cluster with ``n_tenants``;
* ``_arrival_objects`` and ``_decide_batch``: a request carries its tenant
  and no belief, which the engine forms from the tenant table;
* ``reference``: ``reference_tenant.TenantReplay`` replays the run, each
  request read as its ``c0`` and its ``tenant``;
* ``_compare_windows``: the decisions of as many fill windows as timed
  ones are scored besides;
* ``check``: besides ``served.check``'s numbers, ``tenant_table_error``,
  the largest difference between the engine's tenant table at the end and
  the reference's float64 table, each entry as a share of the larger of
  the reference's value and 1 (limit ``check.tenant_table_error``).

``generator.World`` runs each admitted deployment from its tenant's type as
it runs any other. After the run the window's tenant lookups (informed and
cold, from the engine's ``tenant_lookups_total``) reach the per-layer
readers as ``layer.tenant_lookups``, and the info line gains them, the
informed rows, the largest tenant ``mu`` shape at the end and the error of
the program's float32 Gamma ratio at that shape on the device the run used,
against float64 (beside that of a difference of two ``gammaln``).
"""
from __future__ import annotations

import importlib.util
import os
import sys
from types import SimpleNamespace

import numpy as np

DRIVERS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(DRIVERS)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import generator  # noqa: E402
import reference  # noqa: E402
import reference_tenant  # noqa: E402


def _private_served():
    spec = importlib.util.spec_from_file_location(
        "served_for_tenants", os.path.join(DRIVERS, "served.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


served = _private_served()


def draw_arrivals(config: dict, n: int, seed: int) -> dict:
    """``n`` requests as float32 arrays: ``tenant``, its true ``lam``,
    ``mu``, ``sig`` and the request's cores ``c0``."""
    pr, ten = config["priors"], config["tenants"]
    k = int(ten["count"])
    r = generator.rng(seed, 3)
    lam = r.gamma(pr["lam_shape"], 1.0 / pr["lam_rate"], k)
    mu = r.gamma(pr["mu_shape"], 1.0 / pr["mu_rate"], k)
    sig = r.gamma(pr["sig_shape"], 1.0 / pr["sig_rate"], k)
    share = np.arange(1, k + 1, dtype=np.float64) ** -float(ten["zipf_s"])
    tenant = r.choice(k, size=n, p=share / share.sum())
    c0 = 1.0 + r.poisson(sig[tenant])
    out = dict(tenant=tenant, lam=lam[tenant], mu=mu[tenant],
               sig=sig[tenant], c0=c0)
    return {key: v.astype(np.float32) for key, v in out.items()}


def check_config(config: dict) -> None:
    """The reference implements these semantics and no others."""
    if config["policy"]["kind"] != "second":
        raise ValueError("tenant cells implement the second-moment policy")
    if config["prior_mode"] != "global":
        raise ValueError("tenant cells start each tenant from the GLOBAL "
                         "population prior")
    if len(config["capacities"]) != 1 or "tenants" not in config:
        raise ValueError("tenant cells serve one cluster with a tenants "
                         "count")
    from repro.sim.core import TENANT_ZIPF

    if float(config["tenants"]["zipf_s"]) != TENANT_ZIPF:
        raise ValueError(
            f"tenants.zipf_s {config['tenants']['zipf_s']} differs from the "
            f"program's TENANT_ZIPF {TENANT_ZIPF}, which its offline scan "
            "draws tenants with")


def build_engine(config: dict):
    from repro.core import SECOND, geometric_grid, make_policy
    from repro.core.processes import PopulationPriors
    from repro.serve.admission import OnlineAdmissionEngine
    from repro.sim import make_config

    check_config(config)
    cap = float(config["capacities"][0])
    g = config["grid"]
    cfg = make_config(
        capacity=cap, arrival_rate=float(config["arrival_rate_per_h"]),
        horizon_hours=float(config["horizon_h"]), dt=float(config["dt_h"]),
        max_slots=int(config["max_slots"]),
        max_arrivals=int(config["max_arrivals"]), d_points=int(g["d_points"]),
        agg_backend=config["agg_backend"],
        agg_refresh_steps=int(config["agg_refresh_steps"]),
        priors=PopulationPriors(**config["priors"]),
        n_tenants=int(config["tenants"]["count"]))
    policy = make_policy(SECOND, rho=float(config["policy"]["rho"]),
                         capacity=cap)
    grid = geometric_grid(g["t_min_h"], g["t_max_h"], g["points"])
    serving = config["serving"]
    return OnlineAdmissionEngine(
        cfg, grid, SECOND, policy, micro_batch=int(serving["micro_batch"]),
        flush_slo_ms=float(serving["flush_slo_ms"]), scale=None)


def _arrival_objects(host: dict, start: int) -> list:
    from repro.core.processes import DeploymentParams
    from repro.serve.admission import Arrival

    f32 = {k: np.asarray(v, np.float32) for k, v in host.items()}
    out = [None] * start
    for i in range(start, len(f32["c0"])):
        out.append(Arrival(
            c0=float(f32["c0"][i]), bel=None, bel_alt=None,
            params=DeploymentParams(lam=f32["lam"][i], mu=f32["mu"][i],
                                    sig=f32["sig"][i]),
            tenant=int(f32["tenant"][i])))
    return out


def _decide_batch(engine, host: dict, rows: np.ndarray, width: int,
                  rec) -> None:
    from repro.core.processes import DeploymentParams
    from repro.sim.core import ArrivalStream

    n = len(rows)
    if n == 0:
        return
    lanes = np.concatenate([rows, np.full(width - n, rows[0])])
    col = lambda k: np.asarray(host[k], np.float32)[lanes]
    batch = ArrivalStream(
        params=DeploymentParams(lam=col("lam"), mu=col("mu"), sig=col("sig")),
        c0=col("c0"), bel=None, bel_alt=None,
        n_arrivals=np.ones(width, np.int32),
        tenant=col("tenant").astype(np.int32))
    accept = engine.decide_slice(batch, np.arange(width) < n)[:n]
    for i, ok in zip(rows.tolist(), accept):
        rec.record(i, engine.ticks - 1, engine.decisions, bool(ok))


def table_error(table: np.ndarray, want: np.ndarray) -> float:
    """The largest difference of a tenant table from the reference's, each
    entry as a share of the larger of the reference's value and 1."""
    return float(np.max(np.abs(np.asarray(table, np.float64) - want)
                        / np.maximum(np.abs(want), 1.0)))


_served_check = served.check


def check(config, traffic, sched, host_arrivals, issued, rec, n_ticks, seed,
          served_traces, table, world=None):
    """``served.check`` replayed by ``TenantReplay``, and the tenant table
    ``table`` the run ended with held to the reference's."""
    replays = []

    def replay(cfg):
        replays.append(reference_tenant.TenantReplay(cfg))
        return replays[-1]

    served.reference = SimpleNamespace(Replay=replay, BELIEF=("tenant",),
                                       gap=reference.gap)
    checks, info = _served_check(config, traffic, sched, host_arrivals,
                                 issued, rec, n_ticks, seed, served_traces,
                                 world=world)
    checks["tenant_table_error"] = {
        "value": table_error(table, replays[0].table),
        "limit": float(config["check"]["tenant_table_error"])}
    return checks, info


_served_compare = served._compare_windows


def _compare_windows(sched, seed: int, count: int) -> set:
    """``served._compare_windows``'s windows, and ``count`` more drawn from
    the seed among the fill windows. A tenant's row changes a decision only
    where the cluster still admits, and on many seeds this cell's cluster
    has filled to where it admits nothing in the timed window: its fill,
    from an empty cluster, is where the lookup shows."""
    rng = np.random.default_rng((int(seed) % (1 << 64), 11))
    fill = rng.choice(sched.n_fill, size=min(count, sched.n_fill),
                      replace=False)
    return _served_compare(sched, seed, count) | set(fill.tolist())


served._compare_windows = _compare_windows
served.generator = SimpleNamespace(
    schedule=generator.schedule, World=generator.World,
    dense=generator.dense, draw_arrivals=draw_arrivals)
served._arrival_objects = _arrival_objects
served._decide_batch = _decide_batch


def gamma_ratio_error(a: float, nu: float) -> dict:
    """Relative error of Gamma(a+p)/Gamma(a) in float32 on the default
    device, the program's and a difference of two ``gammaln``, against
    float64, at p = nu and 2 nu - 1 (the ratios a refresh packs)."""
    import jax
    import jax.numpy as jnp
    from scipy.special import gammaln

    from repro.core.belief import log_gamma_ratio

    a32 = np.float32(a)
    ratio = jax.jit(log_gamma_ratio)
    naive = jax.jit(lambda x, p: jax.scipy.special.gammaln(x + p)
                    - jax.scipy.special.gammaln(x))
    out = {}
    for name, p in (("nu", nu), ("2nu-1", 2.0 * nu - 1.0)):
        p32 = np.float32(p)
        want = gammaln(float(a32) + float(p32)) - gammaln(float(a32))
        for key, fn in (("program", ratio), ("gammaln_difference", naive)):
            got = float(fn(jnp.float32(a32), jnp.float32(p32)))
            out[f"{key}_p_{name}"] = abs(float(np.expm1(got - want)))
    return out


def run(ctx) -> dict:
    held = {}

    def build(config):
        engine = build_engine(config)
        start = engine.start

        def start_window(*args, **kwargs):
            held["lookups"] = dict(
                engine.metrics_snapshot()["engine"]["tenant_lookups_total"])
            start(*args, **kwargs)

        engine.start = start_window
        held["engine"] = engine
        return engine

    served.build_engine = build
    served.check = lambda *args, **kwargs: check(
        *args, table=held["engine"].tenant_table(), **kwargs)
    out = served.run(ctx)
    engine = held.pop("engine")
    snap = engine.metrics_snapshot()["engine"]
    window = {k: v - held["lookups"][k]
              for k, v in snap["tenant_lookups_total"].items()}
    mu_a_max = float(ctx.config["priors"]["mu_shape"]
                     + engine.tenant_table()[:, 0].max())
    out["layer"]["tenant_lookups"] = window
    out["info"].update(
        tenant_lookups_window=window,
        tenant_rows_informed_end=snap["tenant_rows_informed"],
        tenant_mu_a_max_end=mu_a_max,
        gamma_ratio_error_at_mu_a_max=gamma_ratio_error(
            mu_a_max, float(ctx.config["priors"]["nu"])))
    return out
