"""Served admission: open-loop requests into ``OnlineAdmissionEngine``.

A run of a served cell:

1. set-up: draws the cell's arrivals from the seed (``bench/generator.py``),
   builds the engine from the configuration file (``scale=None``: nothing
   is read from benchmark output), and serves the traffic file's fill
   windows synchronously, which fills the clusters to their operating point
   and compiles every program the window runs;
2. the window: the engine's deadline scheduler runs on its own thread; a
   ticker thread ticks each window at its wall time with the events that
   the deployments admitted so far produce in it (``generator.World``,
   through ``tick(events=...)``), and this thread submits each request at
   its due time. Each request is timed from its due time to the moment its
   future resolves. A traced run serves at most ``TRACE_SECONDS`` of it;
3. the check: after the close, every answer is awaited, the device's peak
   memory read, and the reference (``bench/reference.py``) replays the run:
   every window's utilization and failed scale-outs must match exactly, and
   each decision of the compared windows is held against the reference's.
"""
from __future__ import annotations

import collections
import functools
import gc
import math
import os
import sys
import tempfile
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import generator  # noqa: E402
import reference  # noqa: E402

#: fill windows served through submit and flush (the rest in batches)
SERVED_FILL = 2
#: the longest window a traced run serves: the profiler slows the host
#: about fourfold, and its trace of a longer one takes minutes to read
TRACE_SECONDS = 5.0


class CompileClock:
    """Counts, while active, JAX's compilation work: backend compilations
    (a program found in the persistent cache adds none), and also tracing,
    lowering and persistent-cache loads, by event name; and the host's
    garbage-collector pauses."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        self.events = {}
        self.gc_pauses = []
        self._gc_t = None

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration
        if event.startswith(self.PREFIXES):
            n, s = self.events.get(event, (0, 0.0))
            self.events[event] = (n + 1, s + duration)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc_pauses.append((info["generation"],
                                   time.perf_counter() - self._gc_t))

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        import jax

        gc.callbacks.remove(self._gc)
        jax.monitoring.unregister_event_duration_listener(self._listen)

    def summary(self) -> dict:
        return {
            "compiles_in_window": self.count,
            "compile_s_in_window": self.seconds,
            "compile_events_in_window": self.events,
            "gc_pauses_in_window": len(self.gc_pauses),
            "gc_max_pause_ms": 1e3 * max((p for _, p in self.gc_pauses),
                                         default=0.0),
            "gc_full_in_window": sum(g == 2 for g, _ in self.gc_pauses),
        }


def check_config(config: dict) -> None:
    """The reference implements these semantics and no others."""
    if config["policy"]["kind"] != "second":
        raise ValueError("served cells implement the second-moment policy")
    if config["prior_mode"] != "global":
        raise ValueError("served cells implement the GLOBAL information model")
    if len(config["capacities"]) > 1 and config["router"] != "least_utilized":
        raise ValueError("served fleets implement the least-utilized router")


def build_engine(config: dict):
    from repro.core import SECOND, geometric_grid, make_policy
    from repro.core.policies import fleet_policy
    from repro.core.processes import PopulationPriors
    from repro.serve.admission import OnlineAdmissionEngine
    from repro.sim import make_config, make_fleet_config
    from repro.sim.routing import ROUTERS

    check_config(config)
    caps = [float(c) for c in config["capacities"]]
    g = config["grid"]
    common = dict(
        arrival_rate=float(config["arrival_rate_per_h"]),
        horizon_hours=float(config["horizon_h"]), dt=float(config["dt_h"]),
        max_slots=int(config["max_slots"]),
        max_arrivals=int(config["max_arrivals"]), d_points=int(g["d_points"]),
        agg_backend=config["agg_backend"],
        agg_refresh_steps=int(config["agg_refresh_steps"]),
        priors=PopulationPriors(**config["priors"]))
    rho = float(config["policy"]["rho"])
    router = None
    if len(caps) > 1:
        cfg = make_fleet_config(caps, **common)
        policy = fleet_policy(SECOND, capacities=caps, rho=rho)
        router = ROUTERS[config["router"]]()
    else:
        cfg = make_config(capacity=caps[0], **common)
        policy = make_policy(SECOND, rho=rho, capacity=caps[0])
    grid = geometric_grid(g["t_min_h"], g["t_max_h"], g["points"])
    serving = config["serving"]
    return OnlineAdmissionEngine(
        cfg, grid, SECOND, policy, router=router,
        micro_batch=int(serving["micro_batch"]),
        flush_slo_ms=float(serving["flush_slo_ms"]), scale=None)


def _arrival_objects(host: dict, start: int) -> list:
    """The engine's request objects for requests ``start`` on (``None``
    before: those are decided in batches)."""
    from repro.core.belief import GammaBelief
    from repro.core.processes import DeploymentParams
    from repro.serve.admission import Arrival

    f32 = {k: np.asarray(v, np.float32) for k, v in host.items()}
    out = [None] * start
    for i in range(start, len(f32["c0"])):
        bel = GammaBelief(*(f32[f][i] for f in reference.BELIEF))
        out.append(Arrival(
            c0=float(f32["c0"][i]), bel=bel, bel_alt=bel,
            params=DeploymentParams(lam=f32["lam"][i], mu=f32["mu"][i],
                                    sig=f32["sig"][i])))
    return out


def _decide_batch(engine, host: dict, rows: np.ndarray, width: int,
                  rec) -> None:
    """Decide ``rows`` (at most ``width``) in one batch of ``width`` lanes
    through the engine's batch entry, and record them as the done-callbacks
    record a flush."""
    from repro.core.belief import GammaBelief
    from repro.core.processes import DeploymentParams
    from repro.sim.core import ArrivalStream

    n = len(rows)
    lanes = np.concatenate([rows, np.full(width - n, rows[0] if n else 0)])
    col = lambda k: np.asarray(host[k], np.float32)[lanes]
    bel = GammaBelief(*(col(f) for f in reference.BELIEF))
    batch = ArrivalStream(
        params=DeploymentParams(lam=col("lam"), mu=col("mu"), sig=col("sig")),
        c0=col("c0"), bel=bel, bel_alt=bel,
        n_arrivals=np.ones(width, np.int32))
    if n == 0:
        return
    accept = engine.decide_slice(batch, np.arange(width) < n)[:n]
    for i, ok in zip(rows.tolist(), accept):
        rec.record(i, engine.ticks - 1, engine.decisions, bool(ok))


def next_events(world, log, fleet: bool):
    """The world's events for the engine's next tick."""
    from repro.serve.admission import ExternalEvents

    ev = world.next_events(log)
    return ExternalEvents(**(ev if fleet else {k: v[0]
                                                for k, v in ev.items()}))


class Recorder(generator.DecisionLog):
    """Per-request outcome, written by each future's done-callback on the
    thread that resolves it: the window it was decided in, the flush part it
    was decided with (the engine's decision count after that part), the
    verdict and the time. As a client does, the submitting thread holds each
    future until it is answered and then lets it go; ``wait`` waits for the
    answers of every request submitted."""

    def __init__(self, engine, n: int):
        super().__init__(n)
        self.engine = engine
        self.done_t = np.full(n, np.nan)
        self.sub_t = np.full(n, np.nan)
        self.n_sub = 0
        self.n_done = 0
        self._all = threading.Condition()
        self._held = collections.deque()

    @property
    def submitted(self) -> np.ndarray:
        return np.flatnonzero(~np.isnan(self.sub_t))

    def submit(self, i: int, arrival) -> None:
        self.sub_t[i] = time.perf_counter()
        self.n_sub += 1
        fut = self.engine.submit(arrival)
        fut.add_done_callback(functools.partial(self._done, i))
        self._held.append(fut)
        while self._held and self._held[0].done():
            self._held.popleft()

    def _done(self, i: int, fut) -> None:
        t = time.perf_counter()
        if fut.exception() is None:
            self.record(i, self.engine.ticks - 1, self.engine.decisions,
                        bool(fut.result()))
            self.done_t[i] = t
        with self._all:
            self.n_done += 1
            self._all.notify_all()

    def wait(self, timeout: float) -> None:
        """Until every request submitted is answered, or ``timeout``."""
        with self._all:
            self._all.wait_for(lambda: self.n_done >= self.n_sub,
                               max(timeout, 0.0))


def _sleep_until(t: float) -> None:
    d = t - time.perf_counter()
    if d > 0:
        time.sleep(d)


def _serve_window(engine, sched, arrivals, events, rec, t0, seconds):
    """Tick on a thread of its own, each window with ``events()`` drawn at
    its due time, and submit here, each request at its due time, until the
    close; returns the indices of the requests submitted."""
    period = 1.0 / sched.windows_per_s
    t_end = t0 + seconds
    errors = []

    def ticker():
        try:
            for w in range(sched.n_fill, sched.n_windows):
                due = t0 + (w - sched.n_fill) * period
                if due >= t_end:
                    return
                _sleep_until(due)
                engine.tick(events=events())
        except BaseException as exc:      # reported after the window
            errors.append(exc)

    th = threading.Thread(target=ticker, name="bench-ticker")
    th.start()
    submitted = []
    for i in range(int(sched.first[sched.n_fill]), len(sched.due_s)):
        due = t0 + sched.due_s[i]
        if due >= t_end:
            break
        _sleep_until(due)
        rec.submit(i, arrivals[i])
        submitted.append(i)
    th.join()
    if errors:
        raise errors[0]
    return submitted


def latencies(rec, sched, submitted, t0: float):
    """(latency, lateness) in ms of the ``submitted`` requests: each answered
    request timed from its due time (not from its submission) to the moment
    its answer came, and how late the generator submitted each."""
    due = t0 + sched.due_s[submitted]
    done = rec.done_t[submitted]
    answered = ~np.isnan(done)
    return ((done[answered] - due[answered]) * 1e3,
            (rec.sub_t[submitted] - due) * 1e3)


def _compare_windows(sched, seed: int, count: int) -> set:
    """The windows whose decisions the reference scores: ``count`` drawn from
    the seed among the measured windows, and the last fill window."""
    rng = np.random.default_rng((int(seed) % (1 << 64), 7))
    measured = np.arange(sched.n_fill, sched.n_windows)
    pick = rng.choice(measured, size=min(count, len(measured)), replace=False)
    return set(pick.tolist()) | {sched.n_fill - 1}


def replay(config, sched, host_arrivals, issued, rec, n_ticks, compare):
    """Replay a run in the reference. Returns (the reference's state, the
    widest decision gap, decisions scored, fits disagreements, decisions
    scored within a tenth of the bound, disagreements)."""
    rep = reference.Replay(config)
    shape = (rep.n_c, rep.n_s)
    rho = rep.rho
    decided = np.flatnonzero(rec.window >= 0)
    order = decided[np.lexsort((decided, rec.part[decided],
                                rec.window[decided]))]
    by_window = {}
    for i in order:
        by_window.setdefault(int(rec.window[i]), []).append(int(i))
    widest, scored, fits_wrong, near, differ = 0.0, 0, 0, 0, 0
    arr = {k: np.asarray(v, np.float32).astype(np.float64)
           for k, v in host_arrivals.items()}
    for w in range(n_ticks):
        rep.tick(generator.dense(issued[w], shape), aggregate=w in compare)
        idx = by_window.get(w, [])
        parts = {}
        for i in idx:
            parts.setdefault(int(rec.part[i]), []).append(i)
        for p in sorted(parts):
            rows = parts[p]
            batch = [{k: arr[k][i] for k in ("c0",) + reference.BELIEF}
                     for i in rows]
            served = rec.admit[rows]
            verdicts = rep.flush(batch, served=served)
            for v, ok in zip(verdicts, served):
                if v is None:
                    continue
                scored += 1
                near += abs(v.score - rho) < 0.1 * rho
                g = reference.gap(v, bool(ok), rho)
                differ += g > 0.0
                if math.isinf(g):
                    fits_wrong += 1
                else:
                    widest = max(widest, g)
    rep.finish()
    return rep, widest, scored, fits_wrong, near, differ


def _traces(metrics, fleet: bool):
    """Per cluster: window utilizations and failed scale-outs ``[C, T]``,
    requests admitted and rejected ``[C]``."""
    m = metrics.per_cluster if fleet else metrics
    out = tuple(np.asarray(getattr(m, f)) for f in (
        "util_trace", "fail_trace", "arrivals_accepted", "arrivals_rejected"))
    return out if fleet else tuple(x[None] for x in out)


def run(ctx) -> dict:
    import jax

    config, traffic = ctx.config, ctx.traffic
    seed = ctx.seed
    seconds = min(ctx.seconds, TRACE_SECONDS) if ctx.trace else ctx.seconds
    phases = {"start": time.perf_counter() - ctx.t_start}
    mark = lambda name: phases.__setitem__(
        name, time.perf_counter() - ctx.t_start - sum(phases.values()))
    sched = generator.schedule(config, traffic, seed, seconds)
    n_arr = int(sched.first[-1])
    host_arrivals = generator.draw_arrivals(config, n_arr, seed)
    world = generator.World(config, host_arrivals, seed)
    mark("draw")
    arrivals = _arrival_objects(
        host_arrivals, int(sched.first[max(sched.n_fill - SERVED_FILL - 1, 0)]))
    engine = build_engine(config)
    if ctx.engine_hook is not None:
        ctx.engine_hook(engine)
    fleet = engine.fleet
    rec = Recorder(engine, n_arr)
    events = lambda: next_events(world, rec, fleet)
    mark("engine")

    # fill: the same engine, window by window. Each window's requests are
    # decided through the engine's batch entry (``decide_slice``) in
    # batches of the configuration's ``max_arrivals`` lanes, except in the
    # last ``SERVED_FILL`` windows, which go through submit and flush as the
    # window does and so compile what it runs. The first window's requests
    # wait for the second's tick, as requests do in a window closed before
    # its flush; that closes a window with no decisions, whose step the
    # window may run too.
    width = int(config["max_arrivals"])
    for w in range(sched.n_fill):
        engine.tick(events=events())
        if w == 0:
            continue
        if w < sched.n_fill - SERVED_FILL:
            for v in (w - 1, w) if w == 1 else (w,):
                rows = np.arange(sched.first[v], sched.first[v + 1])
                for j in range(0, len(rows), width):
                    _decide_batch(engine, host_arrivals, rows[j:j + width],
                                  width, rec)
        else:
            lo = sched.first[w - 1 if w == 1 else w]
            for i in range(int(lo), int(sched.first[w + 1])):
                rec.submit(i, arrivals[i])
            engine.flush()
    mark("fill")
    gc.collect()
    gc.freeze()
    fill_ticks = engine.ticks
    engine.start()
    snap0 = engine.metrics_snapshot()["engine"]
    mark("engine_start")

    profile_dir = tempfile.mkdtemp(prefix="bench-trace-") if ctx.trace else None
    with CompileClock() as clock:
        if profile_dir:
            jax.profiler.start_trace(profile_dir)
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_start
        submitted = _serve_window(engine, sched, arrivals, events,
                                  rec, t0, seconds)
        t_close = t0 + seconds
        rec.wait(t_close + ctx.answer_wait_s - time.perf_counter())
        t_trace_end = time.perf_counter()
        if profile_dir:
            jax.profiler.stop_trace()
    engine.stop()
    snap = engine.metrics_snapshot()["engine"]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:ctx.chips])
    metrics = engine.metrics()
    n_ticks = engine.ticks
    traces = _traces(metrics, fleet)
    del engine, metrics

    sub = np.asarray(submitted, np.int64)
    answered = ~np.isnan(rec.done_t[sub])
    lat_ms, late_ms = latencies(rec, sched, sub, t0)
    in_window = int(np.sum(rec.done_t[sub][answered] <= t_close))
    e2e = {
        "decision_p50_ms": float(np.percentile(lat_ms, 50)),
        "decisions_per_s": in_window / seconds,
        "setup_s": setup_s,
    }

    t_ref = time.perf_counter()
    checks, ref_info = check(config, traffic, sched, host_arrivals,
                             world.issued, rec, n_ticks, seed, traces,
                             world=world)
    info = {
        "requests": int(len(sub)), "answered": int(answered.sum()),
        "decision_p95_ms": float(np.percentile(lat_ms, 95)),
        "decision_p99_ms": float(np.percentile(lat_ms, 99)),
        "generator_late_ms_median": float(np.median(late_ms)),
        "generator_late_ms_max": float(np.max(late_ms)),
        **clock.summary(),
        "generator_late_max_at_s": float(
            sched.due_s[sub][np.argmax(late_ms)]) if len(sub) else 0.0,
        "setup_phases_s": phases,
        "setup_s": setup_s, "fill_windows": sched.n_fill, "ticks": n_ticks,
        "window_s": seconds,
        "admitted_share_fill": float(np.mean(
            rec.admit[:int(sched.first[sched.n_fill])])),
        "admitted_share_window": float(np.mean(rec.admit[sub])),
        "window_ticks": n_ticks - fill_ticks,
        "flushes_in_window": snap["n_flushes"] - snap0["n_flushes"],
        "deadline_misses_in_window":
            snap["deadline_misses"] - snap0["deadline_misses"],
        "used_share_window": float(np.mean(
            traces[0][:, fill_ticks:] / np.asarray(config["capacities"])[:, None])),
        "used_share_end": (traces[0][:, -1]
                           / np.asarray(config["capacities"])).tolist(),
        "reference_s": time.perf_counter() - t_ref,
        **ref_info,
    }
    batch = (snap["flush_batch_size"], snap0["flush_batch_size"])
    return {
        "e2e": e2e, "info": info, "checks": checks, "latencies_ms": lat_ms,
        "attempted": int(len(sub)), "failed": int(len(sub) - answered.sum()),
        "memory_peak_bytes": peak, "profile_dir": profile_dir,
        "trace_window_s": t_trace_end - t0,
        "layer": {
            "config": config,
            "window_ticks": n_ticks - fill_ticks,
            "batch_parts": batch[0].total - batch[1].total,
            "batch_requests": batch[0].sum - batch[1].sum,
            "micro_batch": int(config["serving"]["micro_batch"]),
        },
    }


def check(config, traffic, sched, host_arrivals, issued, rec, n_ticks,
          seed, served_traces, world=None):
    """The numbers compared, each with its limit, and what else the replay
    saw. ``issued`` holds each tick's events as the world issued them;
    ``rec`` holds, per request, the window and flush part it was
    decided in (-1: never answered) and its verdict, and ``submitted``, the
    requests made; ``served_traces`` the run's per-cluster window
    utilizations, failed scale-outs and admitted and rejected counts.

    ``exact_mismatches`` counts every one of those that differs from the
    reference, every request admitted that does not fit in the reference's
    state, and every request never answered; ``decision_gap`` is the widest
    gap of a disagreeing decision (``reference.gap``). The share of the
    scored decisions that disagree is reported beside them, not compared:
    it counts every flip of a decision that sits at the bound, however
    small its gap. With the run's ``world``, the info also counts the
    slots where the world's deployments and the reference's differ at the
    end (the traffic's own check: 0 unless it lost track of the cluster)."""
    compare = _compare_windows(sched, seed, int(traffic["compared_windows"]))
    rep, widest, scored, fits_wrong, near, differ = replay(
        config, sched, host_arrivals, issued, rec, n_ticks, compare)
    util_r = np.stack(rep.util_trace, axis=1)
    fail_r = np.stack(rep.fail_trace, axis=1)
    util_p, fail_p, acc_p, rej_p = served_traces
    mism = 0
    for got, want in ((util_p, util_r), (fail_p, fail_r),
                      (acc_p, rep.accepted), (rej_p, rep.rejected)):
        got = np.asarray(got, np.float64)
        mism += (int(np.sum(got != want)) if got.shape == want.shape
                 else int(want.size))
    unanswered = int(np.sum(rec.window[rec.submitted] < 0))
    mism += fits_wrong + unanswered
    limits = config["check"]
    checks = {"exact_mismatches": {"value": mism, "limit": 0},
              "decision_gap": {"value": widest,
                               "limit": float(limits["decision_gap"])}}
    info = {"scored_decisions": scored, "near_bound_decisions": near,
            "disagreements": differ,
            "disagreement_share": differ / scored if scored else 0.0,
            "compared_windows": len(compare),
            "unanswered": unanswered, "fits_disagreements": fits_wrong}
    if world is not None:
        world.settle(rec)
        info["world_slot_mismatches"] = int(np.sum(
            (world.alive != rep.alive) | (world.cores != rep.cores)))
    return checks, info


