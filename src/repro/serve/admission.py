"""Online admission service: the simulator's admission core, served live.

The paper's provider "has to continuously decide" admission as workloads
arrive — this module is that decision loop as a long-lived engine rather
than an offline ``lax.scan``:

  * ``OnlineAdmissionEngine`` holds one device-resident ``CoreState`` (slot
    table + beliefs + maintained aggregate moment curves) and advances it
    with individually **jitted, buffer-donating** steps built from the same
    ``sim.core.make_admission_core`` functions the simulators scan. Because
    the functions are shared — not re-implemented — feeding the engine the
    exact event/arrival sequence drawn by ``make_run`` reproduces the same
    admit/reject decisions and final metrics bit-for-bit (asserted in
    ``tests/test_online_admission.py``).
  * A **micro-batching front-end**: concurrent ``submit()`` calls enqueue
    arrival tickets (plain numpy, no device work on the caller's thread) and
    receive futures; each ``flush()`` coalesces the queue into fixed-width
    decision batches, so a burst of concurrent requests costs one device
    step per ``micro_batch`` of them instead of one aggregate recompute per
    request (the ``naive=True`` ablation path, kept for
    ``benchmarks/serve_bench.py`` to measure against).
  * **Event ingestion between steps**: ``tick()`` advances cluster dynamics
    one ``dt``-hour window — either simulated from the fitted processes
    (``tick(key)``, the benchmark/daemon regime) or applied from *observed*
    departures and scale-out requests (``tick(events=...)``, the production
    regime) — and refreshes the aggregate curves on the blocked
    ``agg_refresh_steps`` schedule, selected from the measured K-curve via
    ``tuning.pick_agg_refresh`` when a scale name is given.

Fleet configurations run the same engine with a leading ``[C]`` cluster
axis and a ``sim.routing.Router`` assigning each micro-batch lane to a
cluster before per-cluster admission, mirroring ``make_fleet_run`` exactly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import re
import threading
import time
import warnings
from concurrent.futures import Future
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.belief import GammaBelief, belief_from_prior, observe_initial_size
from ..core.policies import DecisionDiag, PolicyParams
from ..core.processes import DeploymentParams, sample_params
from ..obs.counters import telemetry_summary
from ..obs.export import HostHistogram, log_buckets
from ..obs.tracing import DecisionTracer, annotate
from ..sim.core import (ArrivalStream, CoreState, FleetConfig, SimConfig,
                        StepOutcome, make_admission_core, slot_mesh)
from ..sim.simulator import (_accumulate_step, _cluster_step_keys,
                             _fleet_metrics, _run_metrics, broadcast_policy)

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One admission request: the per-arrival lane of an ``ArrivalStream``.

    ``params`` are the arrival's true process parameters — used only to
    *simulate* the deployment's future dynamics (benchmarks, the daemon's
    synthetic load); a production deployment's real events arrive through
    ``tick(events=...)`` instead and ``params`` is dead weight there.

    ``tenant`` names the request's tenant for a configuration with
    ``n_tenants``: its belief is then formed on the device from the
    tenant's row, and ``bel``/``bel_alt`` are not read. -1 (the default)
    names none, and the request is decided on the population prior.
    """

    c0: float
    bel: object                    # GammaBelief scalars (provider's prior)
    bel_alt: object                # second mixture component (§7 unlabeled)
    params: object                 # DeploymentParams scalars
    tenant: int = -1               # tenant id (tenant configurations)

    @staticmethod
    def from_stream(stream: ArrivalStream, t: int, a: int) -> "Arrival":
        pick = lambda x: np.asarray(x[t, a])
        return Arrival(c0=float(pick(stream.c0)),
                       bel=jax.tree.map(pick, stream.bel),
                       bel_alt=jax.tree.map(pick, stream.bel_alt),
                       params=jax.tree.map(pick, stream.params),
                       tenant=(-1 if stream.tenant is None
                               else int(pick(stream.tenant))))

    @staticmethod
    def draw(key: jax.Array, cfg: SimConfig) -> "Arrival":
        """Sample one arrival from the population priors (ad-hoc load)."""
        kp, kc = jax.random.split(key)
        params = sample_params(kp, cfg.priors, ())
        c0 = float(1 + jax.random.poisson(kc, params.sig))
        bel = observe_initial_size(belief_from_prior(cfg.priors, ()),
                                   jnp.asarray(c0))
        return Arrival(c0=c0, bel=jax.tree.map(np.asarray, bel),
                       bel_alt=jax.tree.map(np.asarray, bel),
                       params=jax.tree.map(np.asarray, params))


class ExternalEvents(NamedTuple):
    """Observed cluster events for one ``dt``-hour window (production
    ingestion path — replaces the fitted processes' simulated draw).

    All arrays are per-slot ``[S]`` (``[C, S]`` for fleets): ``core_deaths``
    cores lost per deployment, ``spont_death`` whole-deployment shutdowns,
    and the window's scale-out demand (``scaleout_cores`` cores over
    ``n_scaleouts`` requests; grants are decided against capacity in slot
    order, exactly as the simulated path does).
    """

    core_deaths: jax.Array
    spont_death: jax.Array
    scaleout_cores: jax.Array
    n_scaleouts: jax.Array


class _Window(NamedTuple):
    """What the engine carries on the device from one step to the next
    besides the ``CoreState``: the open window and the run's counters that
    the decides fold. Scalars, or ``[C]`` per cluster for fleets."""

    open: jax.Array        # bool: a window is open, its close still due
    out: StepOutcome       # the open window's dynamics
    util: jax.Array        # decision-time utilization
    acc: jax.Array         # requests admitted in the open window
    rej: jax.Array         # requests rejected in the open window
    rej_all: jax.Array     # fleets: requests routed nowhere, whole run
    tick: jax.Array        # int32: windows ticked so far
    key: jax.Array         # the open window's step key
    route_key: Optional[jax.Array]   # fleets: fold_in(key, C)
    lookups: Optional[jax.Array] = None   # tenants: [2] lookups informed
                                          # and cold, whole run


#: a packed flush part is ``[PART_ROWS, width]`` float32: an
#: ``ArrivalStream`` lane's leaves in tree order (``params``, ``c0``,
#: ``bel``, ``bel_alt``; ``n_arrivals`` left out) and then ``valid``
_LANE_TREE = jax.tree.structure(ArrivalStream(
    params=DeploymentParams(0, 0, 0), c0=0, bel=GammaBelief(*[0] * 6),
    bel_alt=GammaBelief(*[0] * 6), n_arrivals=None))
PART_ROWS = _LANE_TREE.num_leaves + 1
#: a tenant configuration's part carries the tenant id in place of the
#: beliefs, which its decide forms from the tenant table:
#: ``[TENANT_PART_ROWS, width]`` (``params``, ``c0``, ``tenant``, ``valid``)
_TENANT_LANE_TREE = jax.tree.structure(ArrivalStream(
    params=DeploymentParams(0, 0, 0), c0=0, bel=None, bel_alt=None,
    n_arrivals=None, tenant=0))
TENANT_PART_ROWS = _TENANT_LANE_TREE.num_leaves + 1


def _unpack_part(packed: jax.Array, tree=_LANE_TREE):
    """``(ArrivalStream, valid)`` of a packed flush part, inside a program."""
    valid = packed[-1] != 0.0
    stream = jax.tree.unflatten(tree, list(packed[:-1]))
    if stream.tenant is not None:
        stream = stream._replace(tenant=stream.tenant.astype(jnp.int32))
    return stream._replace(n_arrivals=valid.astype(jnp.int32)), valid


def _unpack_events(packed: jax.Array) -> ExternalEvents:
    """The ``ExternalEvents`` of a packed ``[4, ...]`` float32 tick buffer,
    inside a program."""
    return ExternalEvents(core_deaths=packed[0], spont_death=packed[1] != 0.0,
                          scaleout_cores=packed[2], n_scaleouts=packed[3])


def _pack_events(events: ExternalEvents) -> np.ndarray:
    """One window's events as one ``[4, ...]`` float32 host buffer. The
    counts are whole numbers below 2**24, which float32 holds exactly."""
    packed = np.empty((4,) + np.shape(events.core_deaths), np.float32)
    for row, field in zip(packed, events):
        row[...] = field
    return packed


class OnlineAdmissionEngine:
    """Long-lived micro-batched admission engine over one ``AdmissionCore``.

    Protocol (one ``dt``-hour window per ``tick``, decisions in between)::

        eng = OnlineAdmissionEngine(cfg, grid, SECOND, policy)
        fut = eng.submit(Arrival.draw(key, cfg))   # any thread, any time
        eng.tick(step_key)                         # dynamics + agg refresh
        eng.flush()                                # decide pending batch
        fut.result()                               # -> bool (admitted?)
        ...
        eng.metrics()                              # RunMetrics so far

    The slot/belief/aggregate state lives on device as one ``CoreState``
    pytree and is **donated** through every jitted step, so a tick or a
    micro-batch decision never allocates a second copy of the slot table.
    ``cfg`` may be a ``SimConfig`` (single cluster) or ``FleetConfig``
    (leading ``[C]`` axis + routing). ``naive=True`` selects the ablation
    front-end: one full aggregate recompute + width-1 decision per request
    (what admission costs without the maintained incremental aggregate).

    Scaling and latency knobs:

      * ``shards=N`` shards the slot table over N devices via the
        ``sim.core.slot_mesh`` lane (single-cluster engines only): every
        jitted step runs as a ``shard_map`` with per-shard moment-curve
        evaluation and the unsharded path's exact reduction order, so the
        sharded engine's decisions and metrics are **bit-for-bit** equal to
        the unsharded engine's — one engine scales state with device count
        instead of being capped by one device's ``max_slots``.
      * ``flush_slo_ms=L`` replaces caller-driven flushing with the
        deadline scheduler (see ``start``/``_deadline_loop``): partial
        micro-batches fire when the oldest pending request approaches its
        L-millisecond decision SLO, full batches when ``micro_batch``
        requests are queued. Misses are counted in
        ``metrics_snapshot()["engine"]["deadline_misses"]``.
      * ``seed`` roots the engine's key chain: the observed-events tick
        path derives its per-window key by ``fold_in(PRNGKey(seed), tick)``
        so distinct engines/restarts draw decorrelated belief noise.

    Observability: with ``cfg.telemetry`` the ``CoreState`` carries the
    device telemetry rider through every step, and ``metrics_snapshot()``
    exports it (plus the host-side phase counters below and queue/pump
    gauges) without synchronizing the pump — that is what the daemon's
    ``/metrics`` endpoint serves. The host counters are always on and read
    ``time.monotonic``: a request's submit→answer time splits into its
    queue wait (submit to the drain of the flush that takes it) and its
    answer time (drain to its future resolved); each flush part into its
    decide wait (jit call returned to accept mask on the host) and the
    rest; ``tick`` and ``flush`` record how long they waited for the state
    lock and ``tick`` how long it held it. The same phases are
    ``repro.engine.*`` profiler spans (``obs.tracing.annotate``). An attached
    ``obs.tracing.DecisionTracer`` additionally receives one structured
    record per ``submit``-path decision (single-cluster engines include
    the policy score via the traced decide path), and an attached
    ``tuning.drift.DriftDetector`` is fed the between-scrape observable
    deltas so prior drift surfaces on the same endpoint.
    """

    def __init__(self, cfg, grid, policy_kind: int, policy: PolicyParams, *,
                 router=None, micro_batch: Optional[int] = None,
                 naive: bool = False, scale: Optional[str] = None,
                 tracer: Optional[DecisionTracer] = None,
                 drift_detector=None, shards: Optional[int] = None,
                 flush_slo_ms: Optional[float] = None, seed: int = 0):
        self.fleet = isinstance(cfg, FleetConfig)
        base = cfg.base if self.fleet else cfg
        if scale is not None:
            from ..tuning import pick_agg_refresh
            base = base._replace(agg_refresh_steps=pick_agg_refresh(
                scale, fallback=base.agg_refresh_steps,
                n_steps=base.n_steps))
        self.cfg = FleetConfig(base=base, capacities=cfg.capacities) \
            if self.fleet else base
        self.base = base
        self.n_shards = int(shards or 1)
        if self.n_shards > 1 and self.fleet:
            raise ValueError(
                "shards= shards one cluster's slot table over devices; "
                "fleet engines already spread state over the cluster axis "
                "— run one sharded engine per cluster instead")
        self.tenants = base.n_tenants > 0
        if self.tenants and (self.fleet or self.n_shards > 1):
            raise ValueError(
                "n_tenants > 0 is served as one unsharded cluster: the "
                "tenant table is neither routed over a fleet nor sharded "
                "over devices (fleets and shards= are out of scope for "
                "tenant configurations)")
        mesh = slot_mesh(self.n_shards) if self.n_shards > 1 else None
        self.core = make_admission_core(base, grid, policy_kind, mesh=mesh)
        self.k_refresh = base.agg_refresh_steps
        if flush_slo_ms is not None and flush_slo_ms <= 0:
            raise ValueError("flush_slo_ms must be positive")
        self.flush_slo_s = (None if flush_slo_ms is None
                            else float(flush_slo_ms) / 1e3)
        self.deadline_misses = 0
        self._flush_cost_s = 0.0    # EWMA of observed flush wall time
        self.naive = naive
        self.width = int(micro_batch or base.max_arrivals)
        self.n_c = self.cfg.n_clusters if self.fleet else 1
        # every input of the engine's programs lives where they run: the
        # sharded engine's on each device of its mesh, so no call moves them
        self._sharding = (None if mesh is None else
                          jax.sharding.NamedSharding(
                              mesh, jax.sharding.PartitionSpec()))
        put = lambda x: jax.device_put(x, self._sharding)
        self._base_key = put(jax.random.PRNGKey(seed))
        self._caps = put(jnp.asarray(
            self.cfg.capacities if self.fleet else base.capacity,
            jnp.float32))
        if self.fleet:
            from ..sim.routing import LeastUtilizedRouter
            self.router = LeastUtilizedRouter() if router is None else router
            policy = broadcast_policy(policy, self.n_c)
        self.policy = put(policy)

        # -- engine state (owned by the engine thread) ----------------------
        cs = self.core.init()
        if self.fleet:
            cs = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.n_c,) + x.shape), cs)
        self._cs: CoreState = cs
        zero = jnp.zeros((self.n_c,) if self.fleet else (), jnp.float32)
        self._win = put(_Window(
            open=jnp.zeros((), bool),
            out=StepOutcome(util=zero, failed=zero, n_requests=zero,
                            departed=zero),
            util=zero, acc=zero, rej=zero, rej_all=jnp.zeros((), jnp.float32),
            tick=jnp.zeros((), jnp.int32), key=self._base_key,
            route_key=self._base_key if self.fleet else None,
            lookups=jnp.zeros(2, jnp.float32) if self.tenants else None))
        self._open = False                        # mirrors _win.open
        self.ticks = 0
        self.decisions = 0
        self._util_trace: list = []
        self._fail_trace: list = []
        self._lane_tree = _TENANT_LANE_TREE if self.tenants else _LANE_TREE
        self._pad_part = self._pad_template(1 if naive else self.width)
        self._j_metrics: dict = {}                # horizon -> assembly

        # -- micro-batch front-end ------------------------------------------
        self._pending: list = []                  # [(Arrival, Future, t_sub)]
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._pump: Optional[threading.Thread] = None
        self._stop = threading.Event()

        # -- observability --------------------------------------------------
        # one reentrant lock serializes every jit-call-and-reassign of the
        # donated CoreState against metrics_snapshot's jnp.copy — without it
        # a snapshot racing the pump could read already-donated buffers
        self._state_lock = threading.RLock()
        self.tracer = tracer
        if self.flush_slo_s is not None:
            # SLO-anchored buckets: the SLO itself is a bucket edge, so the
            # interpolated p99 certifies SLO attainment (p99 <= SLO exactly
            # when no observation crossed the SLO edge) instead of smearing
            # sub-SLO latencies into a coarse decade-wide default bucket
            slo = self.flush_slo_s
            hist = lambda: HostHistogram(
                log_buckets(slo / 512.0, slo, 10) + (2.0 * slo, 4.0 * slo))
        else:
            hist = HostHistogram
        self._hist_latency = hist()               # submit->decision, seconds
        self._hist_queue_wait = hist()            # submit->drain
        self._hist_answer = hist()                # drain->future resolved
        self._hist_decide_wait = hist()           # jit returned->accept mask
        self._hist_tick_host = hist()             # tick's lock held
        self._hist_batch = HostHistogram(
            log_buckets(1.0, float(max(self.width, 2)), 8))
        self._part_host_s = 0.0                   # flush parts minus waits
        self._decide_wait_s = 0.0                 # the last decide's wait
        self._lock_wait = {"flush": [0.0, 0], "tick": [0.0, 0]}
        # device calls (programs run, explicit copies in and out) per step
        self._device_calls = {"tick": [0, 0], "part": [0, 0]}
        self.n_flushes = 0
        self.n_refreshes = 0
        self._pump_busy_s = 0.0
        self._req_id = 0
        self._last_diag = None                    # DecisionDiag of last slice
        # live drift detection: a tuning.drift.DriftDetector fed the obs
        # deltas between metrics_snapshot scrapes (the scrape cadence IS the
        # detector's window — monitoring-driven, zero decision-path cost)
        if drift_detector is not None and not base.telemetry:
            raise ValueError("drift_detector requires cfg.telemetry=True "
                             "(the detector consumes the telemetry rider's "
                             "observable totals)")
        self.drift = drift_detector
        self._drift_prev_obs: Optional[dict] = None
        self._policy_info = {
            "kind": np.asarray(policy.kind).tolist(),
            "threshold": np.asarray(policy.threshold).tolist(),
            "rho": np.asarray(policy.rho).tolist(),
        }

        self._build_jit()

    # ------------------------------------------------------------------ jit

    def _build_jit(self):
        core, cfg, n_c, caps = self.core, self.base, self.n_c, self._caps
        fleet, base_key = self.fleet, self._base_key
        f32 = jnp.float32

        def close(cs, win):
            """The open window's close, as the offline scan ends a step; a
            no-op when no window is open."""
            slots, util_end = _accumulate_step(cs.slots, win.out, win.acc,
                                               win.rej, cfg.dt)
            slots = jax.tree.map(lambda new, old: jnp.where(win.open, new, old),
                                 slots, cs.slots)
            return cs._replace(slots=slots), util_end

        def opened(win, out, key):
            zero = jnp.zeros_like(win.acc)
            return win._replace(
                open=jnp.ones((), bool), out=out, util=out.util, acc=zero,
                rej=zero, tick=win.tick + 1, key=key,
                route_key=jax.random.fold_in(key, n_c) if fleet else None)

        def apply_observed(cap, cs, ev):
            return core.apply_observed(cs, ev, cap)

        if fleet:
            apply_observed = jax.vmap(apply_observed)
        unpack = lambda packed: _unpack_part(packed, self._lane_tree)

        # one program per tick besides the refresh: the previous window's
        # close, this window's dynamics and its key. The observed-events
        # program keeps the name the benchmark's trace reduction counts
        # ticks by (jit__ingest_one)
        def _ingest_one(caps, cs, xs):
            packed, win = xs
            cs, util_end = close(cs, win)
            cs, out = apply_observed(caps, cs, _unpack_events(packed))
            # derive from the engine's seed chain: PRNGKey(tick) would be
            # identical across engines, fleet clusters and restarts,
            # perfectly correlating any downstream belief noise
            key = jax.random.fold_in(base_key, win.tick)
            return cs, (opened(win, out, key), util_end)

        def _simulate_one(key, cs, win):
            cs, util_end = close(cs, win)
            if fleet:
                cs, out = jax.vmap(
                    lambda cap, k, cs_c: core.apply_events(k, cs_c, cap))(
                        caps, _cluster_step_keys(key, n_c), cs)
            else:
                cs, out = core.apply_events(key, cs)
            return cs, (opened(win, out, key), util_end)

        self._j_ingest = jax.jit(_ingest_one, donate_argnums=(1,))
        self._j_tick = jax.jit(_simulate_one, donate_argnums=(1,))
        refresh = (jax.vmap(core.refresh_aggregates) if fleet
                   else core.refresh_aggregates)
        self._j_refresh = jax.jit(refresh, donate_argnums=(0,))

        # metrics()' own close. No donation: the engine keeps referencing
        # the aggregate buffers of the CoreState it passes in
        def close_window(cs, win):
            cs, util_end = close(cs, win)
            return cs.slots, util_end, win._replace(open=jnp.zeros((), bool))

        self._j_close = jax.jit(close_window)

        # a decide takes one packed part and returns the host's results as
        # one array: the accept mask (with the traced decide's diagnostics
        # stacked under it); the window's counters stay on the device
        def folded(cs, win, accept, valid, informed):
            # post-placement utilization, so a second flush inside the
            # same window admits against the already-placed arrivals
            util = jnp.sum(cs.slots.cores * cs.slots.alive.astype(f32))
            n_valid = jnp.sum(valid.astype(f32))
            n_acc = jnp.sum(accept.astype(f32))
            n_rej = n_valid - n_acc
            win = win._replace(util=util, acc=win.acc + n_acc,
                               rej=win.rej + n_rej)
            if informed is not None:
                n_inf = jnp.sum((informed & valid).astype(f32))
                win = win._replace(lookups=win.lookups + jnp.stack(
                    [n_inf, n_valid - n_inf]))
            return win

        if not fleet:
            def decide(policy, cs, win, packed):
                batch, valid = unpack(packed)
                batch, informed = core.lookup(cs, batch)
                cand = core.candidates(batch)
                cs, accept = core.decide_batch(policy, cs, win.util, cand,
                                               batch, valid)
                return cs, folded(cs, win, accept, valid, informed), accept

            def decide_traced(policy, cs, win, packed):
                batch, valid = unpack(packed)
                batch, informed = core.lookup(cs, batch)
                cand = core.candidates(batch)
                cs, accept, diag = core.decide_batch_traced(
                    policy, cs, win.util, cand, batch, valid)
                rows = jnp.stack([jnp.broadcast_to(x, accept.shape).astype(f32)
                                  for x in (accept, *diag)])
                return cs, folded(cs, win, accept, valid, informed), rows

            self._j_decide_traced = jax.jit(decide_traced,
                                            donate_argnums=(1, 2))
        else:
            def fleet_decide(policy, cs, win, packed):
                from ..sim.routing import RouteContext

                batch, valid = unpack(packed)
                cand = core.candidates(batch)
                assign = self.router.route(win.route_key, RouteContext(
                    cand=cand, c0=batch.c0, valid=valid, agg_el=cs.agg_el,
                    agg_vl=cs.agg_vl, util=win.util, capacities=caps,
                    policy=policy))
                assign = jnp.clip(assign, 0, n_c)   # sentinel n_c = nowhere
                mask = valid[None, :] & (
                    assign[None, :] == jnp.arange(n_c)[:, None])
                rej_all = win.rej_all + jnp.sum(
                    (valid & (assign == n_c)).astype(f32))
                cs, accept = jax.vmap(
                    lambda pol_c, cs_c, u_c, m_c: core.decide_batch(
                        pol_c, cs_c, u_c, cand, batch, m_c))(
                            policy, cs, win.util, mask)
                n_acc = jnp.sum(accept.astype(f32), axis=1)
                n_rej = jnp.sum(mask.astype(f32), axis=1) - n_acc
                util = jnp.sum(cs.slots.cores * cs.slots.alive.astype(f32),
                               axis=-1)
                win = win._replace(util=util, acc=win.acc + n_acc,
                                   rej=win.rej + n_rej, rej_all=rej_all)
                return cs, win, jnp.any(accept, axis=0)

            decide = fleet_decide

        def naive_decide(policy, cs, win, packed):
            # ablation: full O(slots * grid) aggregate recompute, then a
            # width-1 decision — the cost of admission without the
            # incrementally-maintained aggregate
            return decide(policy, refresh(cs), win, packed)

        # the window is donated too: what a decide leaves unchanged then
        # aliases its input on the device instead of being copied
        self._j_decide = jax.jit(decide, donate_argnums=(1, 2))
        self._j_naive = jax.jit(naive_decide, donate_argnums=(1, 2))

    # ------------------------------------------------------- step protocol

    def tick(self, key: Optional[jax.Array] = None,
             events: Optional[ExternalEvents] = None):
        """Advance cluster dynamics one ``dt``-hour window.

        Closes the previous decision window (folding its counters into the
        metric accumulators), refreshes the aggregate curves when the
        blocked ``agg_refresh_steps`` schedule says so, then applies this
        window's deaths / scale-out grants / belief updates — simulated from
        the fitted processes under ``key``, or observed via ``events``.

        On the device that is the refresh, when due, and one program for
        the rest (``_j_ingest`` or ``_j_tick``); observed events travel in
        one packed float32 copy, made before the state lock is taken.
        """
        if (key is None) == (events is None):
            raise ValueError("tick() needs exactly one of key= or events=")
        calls = 1                                 # the tick's program
        if events is not None:
            with annotate("repro.engine.tick.events"):
                packed = self._put(_pack_events(events))
            calls += 1
        elif not isinstance(key, jax.Array):
            key = self._put(key)
            calls += 1
        with self._locked("tick"), annotate("repro.engine.tick"):
            t0 = time.monotonic()
            if self.ticks % self.k_refresh == 0 and not self.naive:
                with annotate("repro.engine.refresh"):
                    self._cs = self._j_refresh(self._cs)
                self.n_refreshes += 1
                calls += 1
            with annotate("repro.engine.tick.ingest"):
                if events is not None:
                    self._cs, (win, util_end) = self._j_ingest(
                        self._caps, self._cs, (packed, self._win))
                else:
                    self._cs, (win, util_end) = self._j_tick(
                        key, self._cs, self._win)
            with annotate("repro.engine.tick.close"):
                if self._open:
                    self._util_trace.append(util_end)
                    self._fail_trace.append(self._win.out.failed)
                self._win, self._open = win, True
            self.ticks += 1
            self._count_calls("tick", calls)
            self._hist_tick_host.observe(time.monotonic() - t0)

    def _put(self, x):
        """One explicit host-to-device copy, to where the programs run."""
        return jax.device_put(x, self._sharding)

    def _count_calls(self, step: str, n: int) -> None:
        stat = self._device_calls[step]
        stat[0] += n
        stat[1] += 1

    @contextlib.contextmanager
    def _locked(self, taker: str):
        """Hold ``_state_lock`` as ``taker``'s outermost acquire, counting
        the wait for it under ``lock_wait_seconds{taker}``."""
        t0 = time.monotonic()
        waiting = annotate("repro.engine.lock_wait")
        waiting.__enter__()
        # acquired by a with statement, not an acquire() call: the
        # profiler's Python tracer records such a call as a span of its own,
        # which would cover the wait in place of the lock_wait span
        with self._state_lock:
            waiting.__exit__(None, None, None)
            stat = self._lock_wait[taker]
            stat[0] += time.monotonic() - t0
            stat[1] += 1
            yield

    def _close_window(self):
        with self._state_lock:
            if not self._open:
                return
            failed = self._win.out.failed
            slots, util_end, self._win = self._j_close(self._cs, self._win)
            self._cs = self._cs._replace(slots=slots)
            self._util_trace.append(util_end)
            self._fail_trace.append(failed)
            # the next tick's program does not close a closed window again,
            # so metrics() followed by tick() cannot double-count it
            self._open = False

    # ------------------------------------------------- micro-batch frontend

    def submit(self, arrival: Arrival) -> Future:
        """Enqueue one admission request; resolves to ``bool`` (admitted)
        at the next ``flush``. Thread-safe and device-free: callers hand
        over plain numpy scalars, the engine thread does all jax work."""
        fut: Future = Future()
        with self._lock:
            self._pending.append((arrival, fut, time.monotonic()))
            self._work.notify()
        return fut

    @property
    def n_pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def flush(self) -> int:
        """Decide every pending request in fixed-width micro-batches (or one
        by one on the naive ablation path); resolves their futures. Returns
        the number of decisions made.

        The whole drain runs under ``_state_lock``: the open-window check and
        the decides it gates are one critical section, so a concurrent
        ``tick()``/``metrics()`` cannot close the window mid-flight. A chunk
        that raises fails every remaining future with the exception instead
        of leaving callers blocked forever."""
        with self._locked("flush"):
            if not self._open:
                raise RuntimeError("flush() before the first tick()")
            with self._lock:
                pending, self._pending = self._pending, []
            t0 = time.monotonic()
            if not pending:
                return 0
            chunk = 1 if self.naive else self.width
            done = 0
            answered = []        # each answer's time, observed after the drain
            try:
                with annotate("repro.engine.flush"):
                    for i in range(0, len(pending), chunk):
                        part = pending[i:i + chunk]
                        with annotate("repro.engine.flush.part"):
                            t_part = time.monotonic()
                            accept = self._decide([a for a, _, _ in part])
                            with annotate("repro.engine.flush.resolve"):
                                self._trace_part(part, accept)
                                for (_, fut, _), ok in zip(part, accept):
                                    fut.set_result(bool(ok))
                                    answered.append(time.monotonic())
                            self._part_host_s += (time.monotonic() - t_part
                                                  - self._decide_wait_s)
                        done = i + len(part)
            except BaseException as exc:
                for _, fut, _ in pending[done:]:
                    if not fut.done():
                        fut.set_exception(exc)
                raise
            # the phases are observed once every future is resolved, so no
            # request waits on the histograms of those before it
            for (_, _, t_sub), t_ans in zip(pending, answered):
                self._hist_queue_wait.observe(t0 - t_sub)
                self._hist_answer.observe(t_ans - t0)
            cost = time.monotonic() - t0
            self._flush_cost_s = (cost if self._flush_cost_s == 0.0
                                  else 0.8 * self._flush_cost_s + 0.2 * cost)
            self.n_flushes += 1
        return len(pending)

    def _trace_part(self, part: list, accept: np.ndarray) -> None:
        """Record one decided micro-batch chunk: submit→decision latency
        into the host histogram, plus (when a tracer is attached) one
        structured record per decision with the policy score/threshold from
        the traced decide path. The diag arrays reach the host in the
        part's one fetch, stacked under its accept mask — indexing device
        arrays per record would cost one device→host sync per decision."""
        t_dec = time.monotonic()
        diag = self._last_diag
        with self._state_lock:
            self._hist_batch.observe(float(len(part)))
            for j, ((_, _, t_sub), ok) in enumerate(zip(part, accept)):
                lat = t_dec - t_sub
                self._hist_latency.observe(lat)
                if self.flush_slo_s is not None and lat > self.flush_slo_s:
                    self.deadline_misses += 1
                if self.tracer is None:
                    continue
                self._req_id += 1
                rec = dict(step=self.ticks, req_id=self._req_id,
                           policy_kind=self._policy_info["kind"],
                           verdict=bool(ok), latency_s=lat,
                           batch_size=len(part))
                if diag is not None:
                    rec["score"] = diag.score[j]
                    rec["threshold"] = diag.threshold[j]
                    rec["fits"] = diag.fits[j]
                else:
                    rec["threshold"] = self._policy_info["threshold"]
                self.tracer.record(**rec)

    def decide_slice(self, stream_t: ArrivalStream,
                     valid: np.ndarray) -> np.ndarray:
        """Decide one pre-stacked arrival slice of any width (the path the
        equivalence tests and benchmarks drive; ``submit`` + ``flush`` pack
        onto the same program). Returns the ``[A]`` accept mask (for
        fleets: OR over the per-cluster ``[C, A]`` decisions)."""
        valid = np.asarray(valid, bool)
        lanes = stream_t._replace(n_arrivals=None)
        if self.tenants:
            lanes = lanes._replace(bel=None, bel_alt=None)
        leaves = jax.tree.leaves(lanes)
        packed = np.empty((self._lane_tree.num_leaves + 1, valid.shape[0]),
                          np.float32)
        for row, leaf in zip(packed, leaves):
            row[:] = np.asarray(leaf)
        packed[-1] = valid
        return self._decide_packed(packed, int(np.count_nonzero(valid)))

    def _decide_packed(self, packed: np.ndarray, n_valid: int) -> np.ndarray:
        """Decide one packed ``[PART_ROWS, width]`` part
        (``TENANT_PART_ROWS`` with tenants): one copy in, one program, one
        copy out."""
        traced = self.tracer is not None and not self.naive and not self.fleet
        fn = (self._j_naive if self.naive else
              self._j_decide_traced if traced else self._j_decide)
        packed = self._put(packed)
        with self._state_lock:
            # checked under the lock: a concurrent tick()/metrics() closing
            # the window flips _open mid-flight otherwise
            if not self._open:
                raise RuntimeError("decide_slice() before the first tick()")
            self._cs, self._win, out = fn(self.policy, self._cs, self._win,
                                          packed)
            t_ret = time.monotonic()
            with annotate("repro.engine.flush.wait"):
                out = jax.device_get(out)
            self._observe_decide_wait(time.monotonic() - t_ret)
            self._last_diag = None
            if traced:
                self._last_diag = DecisionDiag(fits=out[1] != 0.0,
                                               score=out[2], threshold=out[3])
                out = out[0] != 0.0
            self.decisions += n_valid
            self._count_calls("part", 3)
        return out

    def _observe_decide_wait(self, wait: float) -> None:
        self._decide_wait_s = wait
        self._hist_decide_wait.observe(wait)

    def _decide(self, arrivals: list) -> np.ndarray:
        """Write ``Arrival`` tickets straight into one padded packed part."""
        n = len(arrivals)
        with annotate("repro.engine.flush.stack"):
            packed = self._pad_part.copy()
            if self.tenants:
                for j, a in enumerate(arrivals):
                    packed[:-1, j] = (*a.params, a.c0, a.tenant)
            else:
                for j, a in enumerate(arrivals):
                    packed[:-1, j] = (*a.params, a.c0, *a.bel, *a.bel_alt)
            packed[-1, :n] = 1.0
        return self._decide_packed(packed, n)[:n]

    def _pad_template(self, width: int) -> np.ndarray:
        """A packed part of ``width`` invalid lanes (placeholder arrivals)."""
        if self.tenants:
            lane = (0.0, 1.0, 0.0, 1.0, -1.0)         # lam, mu, sig, c0, tenant
        else:
            bel = belief_from_prior(self.base.priors, ())
            lane = (0.0, 1.0, 0.0, 1.0, *bel, *bel)   # lam, mu, sig, c0, ...
        packed = np.zeros((len(lane) + 1, width), np.float32)
        packed[:-1] = np.asarray(lane, np.float32)[:, None]
        return packed

    # ------------------------------------------------------------ async pump

    def start(self, interval_s: float = 0.001):
        """Run the flush loop on a background thread: concurrent submitters
        get their futures resolved as the engine coalesces the queue.

        Without ``flush_slo_ms`` this is the legacy pump (poll every
        ``interval_s``, drain whatever is queued). With ``flush_slo_ms`` set
        it is the deadline scheduler (``_deadline_loop``): fire a full
        micro-batch the moment ``width`` requests are pending, otherwise
        fire a partial batch when the oldest pending request approaches its
        latency SLO."""
        if self._pump is not None:
            raise RuntimeError("engine pump already running")
        self._stop.clear()
        target = (self._deadline_loop if self.flush_slo_s is not None
                  else lambda: self._pump_loop(interval_s))
        self._pump = threading.Thread(target=target, daemon=True)
        self._pump.start()

    def _pump_loop(self, interval_s: float):
        while not self._stop.is_set():
            if self.n_pending:
                t0 = time.monotonic()
                self.flush()
                self._pump_busy_s += time.monotonic() - t0
            else:
                self._stop.wait(interval_s)

    def _deadline_loop(self):
        """Latency-SLO-aware flush scheduler. Each ``submit()`` stamps its
        enqueue time; the oldest pending request's implicit deadline is
        ``t_sub + flush_slo_s``. Under load the width trigger fires full
        micro-batches (max throughput); at low rate the deadline trigger
        fires a partial batch a safety margin before the oldest request's
        deadline, where the margin is an EWMA of observed flush cost (so
        decisions land before — not at — the SLO) floored at 5% of the SLO.

        The condition's lock is released before flushing: ``flush()`` takes
        ``_state_lock`` then ``_lock``, and ``metrics_snapshot`` holds
        ``_state_lock`` while reading ``n_pending`` — flushing while holding
        ``_lock`` would invert that ordering and deadlock."""
        slo = self.flush_slo_s
        while not self._stop.is_set():
            fire = False
            with self._work:
                while not self._stop.is_set() and not fire:
                    if len(self._pending) >= self.width:
                        fire = True
                    elif self._pending:
                        margin = max(2.0 * self._flush_cost_s, 0.05 * slo)
                        due = self._pending[0][2] + slo - margin
                        wait = due - time.monotonic()
                        if wait <= 0.0:
                            fire = True
                        else:
                            self._work.wait(wait)
                    else:
                        self._work.wait()
            if fire:
                t0 = time.monotonic()
                self.flush()
                self._pump_busy_s += time.monotonic() - t0

    def stop(self):
        if self._pump is None:
            return
        self._stop.set()
        with self._work:
            self._work.notify_all()
        self._pump.join()
        self._pump = None
        self.flush()

    # -------------------------------------------------------------- metrics

    #: the jitted steps whose compiled programs ``metrics_snapshot`` counts
    JIT_STEPS = ("close", "refresh", "tick", "ingest", "decide",
                 "decide_traced", "naive")

    def _compiled_programs(self) -> dict:
        """Executables cached by each jitted step (``_j_<step>``); a step
        that does not exist here, or a JAX without the cache count, is left
        out."""
        out = {}
        for step in self.JIT_STEPS:
            size = getattr(getattr(self, "_j_" + step, None), "_cache_size",
                           None)
            if callable(size):
                out[step] = int(size())
        return out

    def metrics(self):
        """Run-so-far metrics, assembled exactly as the offline drivers
        assemble theirs (same helpers, same arithmetic): ``RunMetrics`` for
        a single cluster, ``FleetMetrics`` for a fleet. After ``n_steps``
        ticks over a ``make_run`` event stream these equal the offline
        result bit-for-bit."""
        self._close_window()
        n_t = len(self._util_trace)
        horizon = (self.base.horizon_hours if n_t == self.base.n_steps
                   else max(n_t, 1) * self.base.dt)
        if n_t:
            util_trace = jnp.stack(self._util_trace)   # [T] / [T, C]
            fail_trace = jnp.stack(self._fail_trace)
        else:
            shape = (0, self.n_c) if self.fleet else (0,)
            util_trace = fail_trace = jnp.zeros(shape)
        return jax.tree.map(np.asarray, self._assemble(horizon)(
            self._cs.slots, util_trace, fail_trace, self._win.rej_all))

    def _assemble(self, horizon: float):
        """The metrics assembly compiled as the offline scans compile
        theirs, with the horizon and the capacities constants of the
        program: run op by op, a division by them can round one ulp away
        from the compiled one."""
        fn = self._j_metrics.get(horizon)
        if fn is None:
            base, caps = self.base, self._caps
            if self.fleet:
                fn = jax.jit(lambda slots, util, fail, rej_all: _fleet_metrics(
                    base, caps, slots, util.T, fail.T, rej_all,
                    horizon_hours=horizon))
            else:
                fn = jax.jit(lambda slots, util, fail, _: _run_metrics(
                    base, slots, util, fail, horizon_hours=horizon))
            self._j_metrics[horizon] = fn
        return fn

    def tenant_table(self) -> Optional[np.ndarray]:
        """A host copy of the tenant table (``[n_tenants,
        len(core.belief.TENANT_COLUMNS)]``, each compensated sum added up
        in float64), or ``None`` without tenants."""
        if not self.tenants:
            return None
        with self._state_lock:
            table = jnp.copy(self._cs.tenants)
        return np.asarray(jax.device_get(table), np.float64).sum(axis=0)

    def metrics_snapshot(self) -> dict:
        """Non-blocking observability snapshot: engine counters, the host
        phase histograms and sums (decision latency, queue wait, answer
        time, decide wait, tick lock hold, flush batch size, lock waits,
        the pump's busy seconds), the device calls of each tick and flush
        part (``device_calls_total``: programs run plus explicit copies in
        and out, summed, and the steps counted), the programs each jitted
        step has compiled, with tenants the decides' tenant lookups
        (``tenant_lookups_total``: informed, where the tenant's row held an
        admitted deployment, and cold) and the rows that hold one
        (``tenant_rows_informed``), and (with ``cfg.telemetry``) the device
        telemetry rider's summary. ``time_s`` is the snapshot's
        ``time.monotonic``, so two snapshots give rates over the interval
        between them.

        With a ``drift_detector`` attached, each scrape additionally feeds
        the detector one window of observable deltas (cumulative telemetry
        obs now minus the previous scrape — so the scrape cadence defines
        the detector window) and exports its state under ``"drift"``.

        Unlike ``metrics()`` this never closes the open window, never
        flushes, and never synchronizes with the pump: it holds the state
        lock only long enough to dispatch a ``jnp.copy`` of the telemetry
        leaves (async, cheap) and to snapshot the host histograms, then
        materializes the copy outside the lock — a Prometheus scrape cannot
        stall admission. Safe from any thread."""
        with self._state_lock:
            tel = self._cs.tel
            tel_copy = (jax.tree.map(jnp.copy, tel)
                        if tel is not None else None)
            if self.tenants:
                lookups = jnp.copy(self._win.lookups)
                sizes = jnp.copy(self._cs.tenants[0, :, -1])
            eng = {
                "n_requests": self.decisions,
                "n_flushes": self.n_flushes,
                "n_refreshes": self.n_refreshes,
                "n_ticks": self.ticks,
                "queue_depth": self.n_pending,
                "pump_busy_seconds": self._pump_busy_s,
                "decision_latency_seconds": self._hist_latency.snapshot(),
                "queue_wait_seconds": self._hist_queue_wait.snapshot(),
                "answer_seconds": self._hist_answer.snapshot(),
                "decide_wait_seconds": self._hist_decide_wait.snapshot(),
                "part_host_seconds": self._part_host_s,
                "tick_host_seconds": self._hist_tick_host.snapshot(),
                "lock_wait_seconds": {
                    taker: {"sum": s, "count": n}
                    for taker, (s, n) in self._lock_wait.items()},
                "device_calls_total": {
                    step: {"sum": s, "count": n}
                    for step, (s, n) in self._device_calls.items()},
                "flush_batch_size": self._hist_batch.snapshot(),
                "deadline_misses": self.deadline_misses,
                "flush_slo_ms": (0.0 if self.flush_slo_s is None
                                 else self.flush_slo_s * 1e3),
                "n_shards": self.n_shards,
                "time_s": time.monotonic(),
            }
        eng["compiled_programs"] = self._compiled_programs()
        if self.tenants:
            informed, cold = np.asarray(jax.device_get(lookups)).tolist()
            eng["tenant_lookups_total"] = {"informed": informed,
                                           "cold": cold}
            eng["tenant_rows_informed"] = int(np.count_nonzero(
                jax.device_get(sizes)))
        snap = {"engine": eng}
        if tel_copy is not None:
            snap["telemetry"] = telemetry_summary(tel_copy)
            if self.drift is not None:
                from ..tuning.drift import channels_from_obs

                obs = snap["telemetry"]["obs"]
                with self._state_lock:
                    prev = self._drift_prev_obs
                    delta = (obs if prev is None else
                             {k: obs[k] - prev.get(k, 0.0) for k in obs})
                    self._drift_prev_obs = dict(obs)
                    self.drift.update(channels_from_obs(delta))
                    snap["drift"] = self.drift.snapshot()
        return snap


# ---------------------------------------------------------------------------
# Tuned operating points: committed BENCH_<scale>.json rows as the source of
# the daemon's default thresholds (same artifact-reader pattern as
# tuning.kcurve — no simulation, no benchmarks import, just the repo root).
# ---------------------------------------------------------------------------

OPERATING_ROW_PREFIX = "serve"

_OP_RE = re.compile(r"theta=(?P<th>[-\d.e+]+) capacity=(?P<cap>[-\d.e+]+)"
                    r" tau=(?P<tau>[-\d.e+]+)")


def operating_row_name(scale_name: str, kind_name: str) -> str:
    return f"{OPERATING_ROW_PREFIX}/{scale_name}/operating_point/{kind_name}"


def format_operating_derived(theta: float, capacity: float,
                             tau: float) -> str:
    return f"theta={theta:.6g} capacity={capacity:.6g} tau={tau:.3g}"


@dataclasses.dataclass(frozen=True)
class OperatingPoint:
    """A tuned (theta, capacity, tau) admission operating point recorded in
    a BENCH artifact. ``theta`` is the threshold (zeroth/first, in cores —
    rescaled linearly when serving a different capacity) or rho (second,
    scale-free)."""

    kind_name: str
    theta: float
    capacity: float
    tau: float

    def theta_for(self, capacity: float) -> float:
        if self.kind_name == "second":
            return self.theta
        return self.theta * (capacity / self.capacity)


def load_operating_point(kind_name: str, scale_name: str = "quick",
                         bench_path: Optional[str] = None
                         ) -> Optional[OperatingPoint]:
    """Read the tuned operating point for a policy kind from the committed
    ``BENCH_<scale>.json`` (or ``bench_path`` / ``$REPRO_BENCH_JSON``).
    Returns ``None`` when no row exists — callers fall back to their
    hand-picked constants (and should warn)."""
    path = bench_path or os.environ.get("REPRO_BENCH_JSON") or os.path.join(
        _REPO_ROOT, f"BENCH_{scale_name}.json")
    try:
        with open(path, encoding="utf-8") as f:
            rows = json.load(f).get("rows", [])
    except (OSError, ValueError):
        return None
    name = operating_row_name(scale_name, kind_name)
    for row in rows:
        if row.get("name") != name:
            continue
        m = _OP_RE.match(row.get("derived", ""))
        if m:
            return OperatingPoint(kind_name=kind_name, theta=float(m["th"]),
                                  capacity=float(m["cap"]),
                                  tau=float(m["tau"]))
    return None


def default_policy_param(kind_name: str, capacity: float,
                         scale_name: str = "quick",
                         bench_path: Optional[str] = None) -> float:
    """The daemon's default threshold/rho: the tuned operating point from
    the committed BENCH artifact, rescaled to ``capacity``; the legacy
    hand-picked constants (0.15 / 0.7 * capacity) only as a warned
    fallback."""
    op = load_operating_point(kind_name, scale_name, bench_path)
    if op is not None:
        return op.theta_for(capacity)
    warnings.warn(
        f"no tuned operating point for policy {kind_name!r} at scale "
        f"{scale_name!r} (run benchmarks.serve_bench to record one); "
        "falling back to hand-picked constants", stacklevel=2)
    return 0.15 if kind_name == "second" else 0.7 * capacity
