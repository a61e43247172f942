#!/usr/bin/env python3
"""Run the admission system's main path once on a TPU, at the paper's scale.

Usage, from the repository root on a host with a TPU:

    python chip_smoke.py               # one chip: served, offline, kernel
    python chip_smoke.py --four-chips  # four chips: the two sharded paths only

One chip, all in this one process (a chip belongs to one process):

  served   the admission daemon's engine (``build_engine`` + ``serve_loop``)
           for a paper-size cluster (c = 20,000 cores, 8,192 slots, 1
           arrival/h, dt = 6 h, Table 2's rho) with the deadline flush
           scheduler on; its decisions and metrics must equal the offline
           ``make_run`` scan fed the same stream and keys, bit for bit;
  offline  ``run_keyed_batch(make_run(PAPER_FULL, 48-point grid))`` over the
           full 3-year horizon (4,380 steps) for a few keys: finite metrics,
           occupancy within capacity, ``alive_end == placed - departed``;
  kernel   the compiled Pallas aggregate kernel (``AGG_KERNEL``) and the
           fused XLA aggregate (``AGG_FUSED``) on the served slot table,
           each against the other and against the per-slot reference
           (``AGG_REFERENCE``) on the chip; each lane's distance from a
           float64 evaluation on the host's CPU backend is reported.

Four chips (``--four-chips``): the served engine with its slot table sharded
over 4 devices against the unsharded engine on one, and ``run_keyed_batch``
over 4 devices against one; both bit for bit.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed check raises, so the script exits non-zero. Where JAX finds no TPU
it exits non-zero before any phase. Times printed are host wall clock,
including compilation where a phase compiles; none is a device time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

#: the served phase's daemon command line: the paper's cluster (PAPER_FULL's
#: capacity, slots, rate and step) with Table 2's tuned rho, so no threshold
#: is read from a benchmark artifact; 340 ticks serve about 2,000 arrivals
SERVED_ARGS = ["--capacity", "20000", "--max-slots", "8192", "--dt", "6",
               "--arrival-rate", "1.0", "--micro-batch", "8",
               "--policy", "second", "--param", "0.112", "--hours", "2040",
               "--flush-slo-ms", "50"]
OFFLINE_KEYS = 4
GRID_POINTS = 48
#: aggregate-curve tolerances, as tests/test_aggregate_fastpath.py holds the
#: kernel against the per-slot reference
EL_RTOL, VL_RTOL = 2e-4, 2e-3


def _check(ok: bool, what: str) -> None:
    """Fail the smoke (``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileClock:
    """Sums JAX's backend-compile durations recorded while active (a program
    found in the persistent compilation cache adds nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


def _emit(line: dict) -> None:
    """Print one phase's result line, tagged with the device it ran on."""
    import jax

    print(json.dumps({**line, "device_kind": jax.devices()[0].device_kind}),
          flush=True)


def _metric_mismatches(a, b) -> list:
    """Names of the ``RunMetrics`` fields that differ bit for bit."""
    import numpy as np

    return [name for name in a._fields
            if not np.array_equal(np.asarray(getattr(a, name)),
                                  np.asarray(getattr(b, name)))]


def _serve(argv: list):
    """Build the daemon's engine from ``argv`` and serve its whole stream."""
    from repro.launch.admission_daemon import build_engine, build_parser, \
        serve_loop

    args = build_parser().parse_args(argv)
    engine, stream, keys, _ = build_engine(args)
    summary = serve_loop(engine, stream, keys)
    return args, engine, stream, summary


def served_phase(argv: list = SERVED_ARGS) -> tuple[dict, object]:
    """The daemon's served path against the offline scan of the same draw.
    Returns (phase line, engine)."""
    import jax
    import numpy as np

    from repro.launch.admission_daemon import POLICY_KINDS
    from repro.sim import make_run

    with CompileClock() as clock:
        t0 = time.perf_counter()
        args, engine, stream, summary = _serve(argv)
        served_s = time.perf_counter() - t0
        online = engine.metrics()
        run = make_run(engine.base, engine.core.grid,
                       POLICY_KINDS[args.policy], record_decisions=True)
        offline, acc_off = run(jax.random.PRNGKey(args.seed), engine.policy)
    mismatches = int(np.sum(np.asarray(acc_off) != summary["accept"]))
    snap = engine.metrics_snapshot()["engine"]
    line = {
        "phase": "served", "slots": engine.base.max_slots,
        "ticks": summary["ticks"], "decisions": summary["decisions"],
        "admitted": summary["admitted"], "mismatches": mismatches,
        "metric_mismatches": _metric_mismatches(offline, online),
        "utilization": float(online.utilization),
        "agg_refresh_K": engine.k_refresh,
        "deadline_misses": snap["deadline_misses"],
        "served_wall_s": served_s, "compile_s": clock.seconds,
    }
    _emit(line)
    due = np.minimum(np.asarray(stream.n_arrivals),
                     summary["accept"].shape[1])
    _check(summary["decisions"] == int(due.sum()), "arrivals left undecided")
    _check(mismatches == 0, "served decisions differ from the offline scan")
    _check(not line["metric_mismatches"], "served metrics differ from offline")
    return line, engine


def _paper_scan(cfg, n_keys: int, grid_points: int):
    """(cfg, make_run scan, keys, Table 2's second-moment policy)."""
    import jax

    from repro.configs.paper_cluster import PAPER_FULL, PAPER_TABLE2
    from repro.core import SECOND, geometric_grid, make_policy
    from repro.sim import make_run

    cfg = PAPER_FULL if cfg is None else cfg
    grid = geometric_grid(cfg.dt, 3 * cfg.horizon_hours, grid_points)
    pol = make_policy(SECOND, rho=PAPER_TABLE2["second_rho"],
                      capacity=cfg.capacity)
    keys = jax.random.split(jax.random.PRNGKey(0), n_keys)
    return cfg, make_run(cfg, grid, SECOND), keys, pol


def offline_phase(cfg=None, n_keys: int = OFFLINE_KEYS,
                  grid_points: int = GRID_POINTS) -> dict:
    """The batched offline scan over the whole horizon, with invariants."""
    import jax
    import numpy as np

    from repro.sim import run_keyed_batch

    cfg, run, keys, pol = _paper_scan(cfg, n_keys, grid_points)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        m = run_keyed_batch(run, keys, pol)
        m = jax.tree.map(np.asarray, m)
        wall = time.perf_counter() - t0
    placed = m.arrivals_accepted - m.slot_overflow
    line = {
        "phase": "offline", "steps": cfg.n_steps, "slots": cfg.max_slots,
        "grid": grid_points, "keys": n_keys,
        "utilization": m.utilization.tolist(),
        "failure_rate": m.failure_rate.tolist(),
        "accepted": m.arrivals_accepted.tolist(),
        "max_occupancy_cores": float(m.util_trace.max()),
        "wall_s": wall, "compile_s": clock.seconds,
    }
    _emit(line)
    for name, val in m._asdict().items():
        _check(bool(np.all(np.isfinite(val))), f"non-finite {name}")
    _check(m.util_trace.shape == (n_keys, cfg.n_steps), "util_trace shape")
    _check(m.util_trace.max() <= cfg.capacity, "occupancy above capacity")
    np.testing.assert_array_equal(m.alive_end, placed - m.n_departed)
    return line


def _f64_oracle(slots, cfg, grid):
    """Aggregate curves of ``slots`` from the per-slot reference formulas in
    float64 on the host's CPU backend, the yardstick for the f32 lanes."""
    import jax
    import numpy as np

    from repro.core import moment_curves

    f64 = lambda x: np.asarray(x, np.float64)
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        cur = moment_curves(jax.tree.map(f64, slots.bel), f64(slots.cores),
                            f64(grid), cfg.priors, d_points=cfg.d_points)
        mask = f64(slots.alive)[:, None]
        return (np.sum(np.asarray(cur.EL) * mask, 0),
                np.sum(np.asarray(cur.VL) * mask, 0))


def kernel_phase(slots, cfg=None, grid_points: int = GRID_POINTS) -> dict:
    """``AGG_KERNEL`` and ``AGG_FUSED`` refreshes of one slot table, each
    held to the per-slot ``AGG_REFERENCE`` on the same device as
    tests/test_aggregate_fastpath.py holds them. How far each lands from a
    float64 evaluation on the host is reported, not asserted: the f32
    gamma-ratio factors of heavily observed slots are ill-conditioned, and
    the TPU's f32 ``gammaln`` is coarser than the CPU's (about 3e-4 of EL
    on the served paper-size table)."""
    import jax
    import numpy as np

    from repro.configs.paper_cluster import PAPER_FULL
    from repro.core import SECOND, geometric_grid
    from repro.kernels.moment_curves.ops import resolve_interpret
    from repro.sim import AGG_FUSED, AGG_KERNEL, AGG_REFERENCE, \
        make_admission_core

    cfg = PAPER_FULL if cfg is None else cfg
    grid = geometric_grid(cfg.dt, 3 * cfg.horizon_hours, grid_points)
    out, text = {}, {}
    with CompileClock() as clock:
        for backend in (AGG_REFERENCE, AGG_FUSED, AGG_KERNEL):
            core = make_admission_core(cfg._replace(agg_backend=backend),
                                       grid, SECOND)
            lowered = jax.jit(core.refresh_aggregates).lower(
                core.init()._replace(slots=slots))
            text[backend] = lowered.as_text()
            cs = lowered.compile()(core.init()._replace(slots=slots))
            out[backend] = (np.asarray(cs.agg_el), np.asarray(cs.agg_vl))
    oracle = _f64_oracle(slots, cfg, grid)
    rel = lambda a, b: float(np.max(np.abs(a - b) / np.maximum(np.abs(b),
                                                               1e-30)))
    line = {
        "phase": "kernel", "slots": int(slots.alive.shape[0]),
        "alive": int(np.sum(np.asarray(slots.alive))), "grid": grid_points,
        "interpret": resolve_interpret(),
        "tpu_custom_call": "tpu_custom_call" in text[AGG_KERNEL],
        "compile_s": clock.seconds,
    }
    pairs = {f"{AGG_FUSED}_vs_{AGG_REFERENCE}": (AGG_FUSED, AGG_REFERENCE),
             f"{AGG_KERNEL}_vs_{AGG_REFERENCE}": (AGG_KERNEL, AGG_REFERENCE),
             f"{AGG_KERNEL}_vs_{AGG_FUSED}": (AGG_KERNEL, AGG_FUSED)}
    for name, (got, want) in pairs.items():
        line[f"{name}_el_max_rel"] = rel(out[got][0], out[want][0])
        line[f"{name}_vl_max_rel"] = rel(out[got][1], out[want][1])
    for backend, (el, vl) in out.items():
        line[f"{backend}_vs_f64_el_max_rel"] = rel(el, oracle[0])
        line[f"{backend}_vs_f64_vl_max_rel"] = rel(vl, oracle[1])
    _emit(line)
    for backend, (el, vl) in out.items():
        _check(bool(np.all(np.isfinite(el)) and np.all(np.isfinite(vl))),
               f"non-finite {backend} curves")
    for got, want in pairs.values():
        np.testing.assert_allclose(out[got][0], out[want][0], rtol=EL_RTOL)
        np.testing.assert_allclose(out[got][1], out[want][1], rtol=VL_RTOL)
    return line


def sharded_served_phase(argv: list = SERVED_ARGS, shards: int = 4) -> dict:
    """The served engine with ``--shards`` against the unsharded engine."""
    import numpy as np

    with CompileClock() as clock:
        _, one, _, s1 = _serve(argv)
        _, many, _, sn = _serve(argv + ["--shards", str(shards)])
        m1, mn = one.metrics(), many.metrics()
    line = {
        "phase": "served_sharded", "shards": many.n_shards,
        "slots": many.base.max_slots, "decisions": sn["decisions"],
        "mismatches": int(np.sum(s1["accept"] != sn["accept"])),
        "metric_mismatches": _metric_mismatches(m1, mn),
        "utilization": float(mn.utilization), "compile_s": clock.seconds,
    }
    _emit(line)
    _check(many.n_shards == shards, "engine not sharded")
    _check(line["mismatches"] == 0, "sharded decisions differ")
    _check(not line["metric_mismatches"], "sharded metrics differ")
    return line


def sharded_offline_phase(cfg=None, n_keys: int = OFFLINE_KEYS,
                          grid_points: int = GRID_POINTS) -> dict:
    """``run_keyed_batch`` over every device against one device."""
    import jax
    import numpy as np

    from repro.sim import run_keyed_batch

    cfg, run, keys, pol = _paper_scan(cfg, n_keys, grid_points)
    with CompileClock() as clock:
        many = run_keyed_batch(run, keys, pol)
        one = run_keyed_batch(run, keys, pol, devices=jax.devices()[:1])
    line = {
        "phase": "offline_sharded", "devices": jax.device_count(),
        "steps": cfg.n_steps, "keys": n_keys,
        "metric_mismatches": _metric_mismatches(one, many),
        "utilization": np.asarray(many.utilization).tolist(),
        "compile_s": clock.seconds,
    }
    _emit(line)
    _check(not line["metric_mismatches"], "device-sharded batch differs")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, on four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        # the kernel phase's float64 yardstick runs on the host's CPU
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax

    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s), JAX found "
              f"{len(devices)} {dev.platform} device(s); refusing to run",
              file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    _emit({"phase": "setup", "devices": len(devices), "compile_cache": cache})
    if args.four_chips:
        sharded_served_phase()
        sharded_offline_phase()
    else:
        _, engine = served_phase()
        offline_phase()
        line = kernel_phase(engine._cs.slots)
        _check(not line["interpret"] and line["tpu_custom_call"],
               "the Pallas kernel did not compile for the chip")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
