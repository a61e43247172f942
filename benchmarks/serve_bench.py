"""Online admission serving rows: decisions/sec and decision latency, live.

Measures ``serve.admission.OnlineAdmissionEngine`` — the long-lived jitted
engine with donated state and a micro-batching front-end — against the naive
per-request path (full aggregate recompute + width-1 decision per arrival,
i.e. admission without the incrementally-maintained aggregate):

  * ``serve/<scale>/engine`` / ``serve/<scale>/naive`` — decisions/sec and
    p50/p99 per-micro-batch decision latency at the reference offered load,
    with the occupied-slot count (cluster state size) recorded.
  * ``serve/<scale>/speedup`` — the micro-batched-over-naive ratio (the
    acceptance bar is >= 2x at the quick preset).
  * ``serve/<scale>/load=...`` — engine throughput vs offered load (arrivals
    per ``dt`` window).
  * ``serve/<scale>/engine|naive/slots=...`` — the same measurement at a
    quarter of the preset's slot table: the naive path's per-decision cost
    scales with cluster state size, the micro-batched path's does not.
  * ``serve/<scale>/sharded`` — the same engine with the slot table sharded
    over every device this process sees (``shards=N``; on CPU give it
    virtual devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``). Decisions are
    bit-for-bit the unsharded engine's, so on virtual devices this row
    measures pure sharding overhead (the win is capacity, not speed). It
    runs in this process: a child process could not reach a chip the
    parent already holds.
  * ``serve/<scale>/deadline_flush`` — the SLO-aware flush scheduler under
    nominal (paced, sub-width) load: recorded p50/p99 submit→decision
    latency from the engine's own histogram, which must meet the configured
    SLO with zero deadline-miss counter increments.
  * ``serve/<scale>/operating_point/<kind>`` — the tuned (theta, capacity,
    tau) operating point re-published from the artifact's own
    ``tuning/calibrate/<kind>`` rows; these rows are what
    ``launch/admission_daemon.py`` reads for its default thresholds
    (``serve.admission.load_operating_point``).

Under ``REPRO_SMOKE=1`` everything shrinks to a seconds-scale synthetic
preset so CI exercises the full row machinery on every PR.
"""
from __future__ import annotations

import json
import os
import re
import time

import jax
import numpy as np

from repro.core import SECOND, make_policy
from repro.serve import (OnlineAdmissionEngine, format_operating_derived,
                         operating_row_name)
from repro.sim import draw_arrival_stream

from .common import SCALES, Scale, csv_row, grid_for, sim_config

SMOKE_SCALE = Scale("smoke", 800.0, 0.05, 60 * 24.0, 24.0, 128, 2, 3,
                    16, 5e-3, agg_refresh=1)

_THETA_RE = re.compile(r"theta=(?P<th>[-\d.e+]+)")

#: fallback rho when the artifact has no tuned second-moment row yet
FALLBACK_RHO = 0.15


def _scale_for(scale_name: str) -> Scale:
    if os.environ.get("REPRO_SMOKE") == "1":
        return SMOKE_SCALE
    return SCALES[scale_name]


def _calibrated_thetas(scale_name: str) -> dict:
    """theta per policy kind from the committed artifact's own
    ``tuning/calibrate/<kind>`` rows (no simulation here)."""
    path = os.environ.get("REPRO_BENCH_JSON") or os.path.join(
        os.path.dirname(__file__), "..", f"BENCH_{scale_name}.json")
    try:
        with open(path, encoding="utf-8") as f:
            rows = json.load(f).get("rows", [])
    except (OSError, ValueError):
        return {}
    out = {}
    for row in rows:
        name = row.get("name", "")
        if not name.startswith("tuning/calibrate/"):
            continue
        m = _THETA_RE.match(row.get("derived", ""))
        if m:
            out[name.rsplit("/", 1)[1]] = float(m["th"])
    return out


def _offered_stream(cfg, width: int, n_slices: int, seed: int):
    """Pre-draw ``n_slices`` saturated width-``width`` arrival slices (the
    offered load; arrival_rate pushed high so every lane is occupied)."""
    stream_cfg = cfg._replace(max_arrivals=width,
                              horizon_hours=n_slices * cfg.dt,
                              arrival_rate=10.0 * width / cfg.dt,
                              agg_refresh_steps=1)
    stream = draw_arrival_stream(jax.random.PRNGKey(seed + 7), stream_cfg)
    return [jax.tree.map(lambda x: x[t], stream) for t in range(n_slices)]


def _measure(cfg, grid, pol, *, naive: bool, width: int, n_ticks: int,
             per_tick: int, seed: int, shards: int = 1) -> dict:
    """Drive the engine ``n_ticks`` windows at ``per_tick`` offered arrivals
    each; time every decision call (micro-batch of ``width``, or width-1 on
    the naive path). Returns decisions/sec, latency quantiles, occupancy."""
    eng = OnlineAdmissionEngine(cfg, grid, SECOND, pol, naive=naive,
                                micro_batch=width,
                                shards=shards if shards > 1 else None)
    bw = 1 if naive else width
    batches_per_tick = max(per_tick // bw, 1)
    slices = _offered_stream(cfg, bw, (n_ticks + 1) * batches_per_tick, seed)
    valid = np.ones(bw, bool)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_ticks + 1)

    # warmup window: compile tick/refresh/decide outside the timed region
    eng.tick(keys[0])
    eng.decide_slice(slices[0], valid)

    it = iter(slices[1:])
    lat = []
    for t in range(n_ticks):
        eng.tick(keys[t + 1])
        for _ in range(batches_per_tick):
            sl = next(it)
            t0 = time.perf_counter()
            eng.decide_slice(sl, valid)      # np accept => device sync
            lat.append(time.perf_counter() - t0)
    lat_s = np.asarray(lat)
    n_dec = lat_s.size * bw
    occupied = int(np.sum(np.asarray(eng._cs.slots.alive)))
    return {
        "decisions_per_s": n_dec / float(np.sum(lat_s)),
        "p50_ms": float(np.percentile(lat_s, 50) * 1e3),
        "p99_ms": float(np.percentile(lat_s, 99) * 1e3),
        "us_per_decision": float(np.sum(lat_s)) * 1e6 / n_dec,
        "occupied": occupied,
        "n_decisions": int(n_dec),
    }


def _measure_telemetry_pair(cfg, grid, pol, *, width: int, n_ticks: int,
                            per_tick: int, seed: int) -> tuple[float, float]:
    """Per-decision p50 microseconds with the telemetry rider off vs on.

    The two engines are driven in lockstep over the *same* ticks and
    arrival slices, with the timing order alternating per batch, so clock
    drift and allocator noise hit both sides equally — two sequential
    ``_measure`` passes cannot resolve a few-percent rider cost. Medians,
    not means: the overhead budget is about the steady-state decision path,
    not stray tail events.
    """
    engines = [
        OnlineAdmissionEngine(cfg._replace(telemetry=tel), grid, SECOND, pol,
                              naive=False, micro_batch=width)
        for tel in (False, True)]
    batches_per_tick = max(per_tick // width, 1)
    slices = _offered_stream(cfg, width, (n_ticks + 1) * batches_per_tick,
                             seed)
    valid = np.ones(width, bool)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_ticks + 1)
    for eng in engines:                    # compile outside the timed region
        eng.tick(keys[0])
        eng.decide_slice(slices[0], valid)
    lat = [[], []]
    it = iter(slices[1:])
    for t in range(n_ticks):
        for eng in engines:
            eng.tick(keys[t + 1])
        for b in range(batches_per_tick):
            sl = next(it)
            order = (0, 1) if (t * batches_per_tick + b) % 2 == 0 else (1, 0)
            for i in order:
                t0 = time.perf_counter()
                engines[i].decide_slice(sl, valid)
                lat[i].append(time.perf_counter() - t0)
    return tuple(float(np.median(lat[i]) * 1e6 / width) for i in (0, 1))


def _measure_sharded(cfg, grid, pol, *, width: int, n_ticks: int,
                     per_tick: int, seed: int) -> dict:
    """``_measure`` with the slot table sharded over all visible devices."""
    shards = jax.device_count()
    m = _measure(cfg, grid, pol, naive=False, width=width, n_ticks=n_ticks,
                 per_tick=per_tick, seed=seed, shards=shards)
    return dict(m, shards=shards)


def _measure_deadline(cfg, grid, pol, *, width: int, slo_ms: float,
                      n_requests: int, seed: int) -> dict:
    """Drive the deadline scheduler at nominal load: paced sub-width
    ``submit()``s (so the deadline trigger — not the width trigger — fires)
    and the engine's own recorded submit→decision latency histogram as the
    measurement. Misses are the engine's counter, not a recomputation."""
    from repro.serve import Arrival

    eng = OnlineAdmissionEngine(cfg, grid, SECOND, pol, micro_batch=width,
                                flush_slo_ms=slo_ms)
    stream_cfg = cfg._replace(max_arrivals=1,
                              horizon_hours=(n_requests + 1) * cfg.dt,
                              arrival_rate=10.0 / cfg.dt,
                              agg_refresh_steps=1)
    stream = draw_arrival_stream(jax.random.PRNGKey(seed + 7), stream_cfg)
    arrivals = [Arrival.from_stream(stream, t, 0)
                for t in range(n_requests + 1)]
    eng.tick(jax.random.PRNGKey(seed))
    # compile the decide path outside the recorded region (decide_slice via
    # _decide does not touch the latency histogram or the miss counter)
    eng._decide([arrivals[0]])
    pace_s = (slo_ms / 1e3) / (2.0 * width)   # nominal: sub-width per SLO
    eng.start()
    futs = []
    for a in arrivals[1:]:
        futs.append(eng.submit(a))
        time.sleep(pace_s)
    for f in futs:
        f.result(timeout=60)
    eng.stop()
    snap = eng.metrics_snapshot()["engine"]
    hist = snap["decision_latency_seconds"]
    return {
        "p50_ms": hist.percentile(0.5) * 1e3,
        "p99_ms": hist.percentile(0.99) * 1e3,
        "mean_us": hist.sum / max(hist.total, 1) * 1e6,
        "misses": int(snap["deadline_misses"]),
        "n_flushes": int(snap["n_flushes"]),
        "n_decisions": int(hist.total),
    }


def _derived(m: dict, width: int, slots: int) -> str:
    return (f"decisions_per_s={m['decisions_per_s']:.0f}"
            f" p50_ms={m['p50_ms']:.3f} p99_ms={m['p99_ms']:.3f}"
            f" occupied={m['occupied']} width={width} slots={slots}"
            f" n={m['n_decisions']}")


def run(scale_name: str = "tiny", seed: int = 0) -> list:
    scale = _scale_for(scale_name)
    smoke = scale.name == "smoke"
    width = 4 if smoke else 16
    n_ticks = 3 if smoke else 8
    per_tick = 4 * width                  # reference offered load
    cfg = sim_config(scale)
    grid = grid_for(scale, cfg)
    thetas = _calibrated_thetas(scale.name)
    rho = thetas.get("second", FALLBACK_RHO)
    pol = make_policy(SECOND, rho=rho, capacity=cfg.capacity)
    rows = []

    # -- headline: micro-batched engine vs naive per-request recompute ------
    m_eng = _measure(cfg, grid, pol, naive=False, width=width,
                     n_ticks=n_ticks, per_tick=per_tick, seed=seed)
    rows.append(csv_row(f"serve/{scale.name}/engine", m_eng["us_per_decision"],
                        _derived(m_eng, width, cfg.max_slots)))
    m_nv = _measure(cfg, grid, pol, naive=True, width=width,
                    n_ticks=n_ticks, per_tick=per_tick, seed=seed)
    rows.append(csv_row(f"serve/{scale.name}/naive", m_nv["us_per_decision"],
                        _derived(m_nv, 1, cfg.max_slots)))
    speedup = m_eng["decisions_per_s"] / m_nv["decisions_per_s"]
    rows.append(csv_row(f"serve/{scale.name}/speedup", 0.0,
                        f"x={speedup:.2f} engine={m_eng['decisions_per_s']:.0f}"
                        f" naive={m_nv['decisions_per_s']:.0f}"
                        f" target_x=2"))

    # -- telemetry overhead: the device rider must be ~free -----------------
    us_off, us_on = _measure_telemetry_pair(cfg, grid, pol, width=width,
                                            n_ticks=2 * n_ticks,
                                            per_tick=per_tick, seed=seed)
    overhead = (us_on / us_off - 1.0) * 100
    rows.append(csv_row(
        f"serve/{scale.name}/telemetry=on", us_on,
        f"p50_us={us_on:.1f} width={width} slots={cfg.max_slots}"
        f" overhead_pct={overhead:.1f} target_pct=3"))
    rows.append(csv_row(
        f"serve/{scale.name}/telemetry=off", us_off,
        "overhead_pct=0.0 rider_compiled_out=true"))

    # -- throughput vs offered load -----------------------------------------
    for mult, label in ((1, "light"), (16, "heavy")):
        m = _measure(cfg, grid, pol, naive=False, width=width,
                     n_ticks=n_ticks, per_tick=mult * width, seed=seed)
        rows.append(csv_row(
            f"serve/{scale.name}/load={mult * width}",
            m["us_per_decision"], _derived(m, width, cfg.max_slots)))

    # -- cluster state size: a quarter of the slot table --------------------
    small = cfg._replace(max_slots=max(cfg.max_slots // 4, width))
    for naive, tag in ((False, "engine"), (True, "naive")):
        m = _measure(small, grid, pol, naive=naive, width=width,
                     n_ticks=n_ticks, per_tick=per_tick, seed=seed)
        rows.append(csv_row(
            f"serve/{scale.name}/{tag}/slots={small.max_slots}",
            m["us_per_decision"],
            _derived(m, 1 if naive else width, small.max_slots)))

    # -- device-sharded slot table over every visible device ----------------
    m_sh = _measure_sharded(cfg, grid, pol, width=width, n_ticks=n_ticks,
                            per_tick=per_tick, seed=seed)
    rows.append(csv_row(
        f"serve/{scale.name}/sharded", m_sh["us_per_decision"],
        f"{_derived(m_sh, width, cfg.max_slots)} shards={m_sh['shards']}"))

    # -- deadline-aware flush scheduler at nominal load ---------------------
    slo_ms = 200.0 if smoke else 250.0
    m_dl = _measure_deadline(cfg, grid, pol, width=width, slo_ms=slo_ms,
                             n_requests=6 * width, seed=seed)
    rows.append(csv_row(
        f"serve/{scale.name}/deadline_flush", m_dl["mean_us"],
        f"p50_ms={m_dl['p50_ms']:.3f} p99_ms={m_dl['p99_ms']:.3f}"
        f" slo_ms={slo_ms:.0f} misses={m_dl['misses']}"
        f" n_flushes={m_dl['n_flushes']} n={m_dl['n_decisions']}"
        f" target_misses=0"))

    # -- tuned operating points for the daemon ------------------------------
    for kind_name, theta in sorted(thetas.items()):
        rows.append(csv_row(
            operating_row_name(scale.name, kind_name), 0.0,
            format_operating_derived(theta, cfg.capacity, scale.tau)))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
