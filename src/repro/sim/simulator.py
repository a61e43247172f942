"""Monte-Carlo cluster simulator (paper §5): lax.scan over time, vmap over runs.

Deployments live in a fixed slot array (jit/vmap-friendly replacement for the
paper's dynamic deployment lists — see DESIGN.md "hardware adaptation"). Each
step of length ``dt`` hours:

  1. core deaths (exact binomial thinning) + spontaneous shutdown (M process)
  2. scale-out requests; granted greedily in slot order while the cluster has
     capacity, otherwise logged as SLA failures (entire request fails)
  3. belief updates from the observed events (conjugate, core.belief)
  4. arrivals (Poisson, capped at ``max_arrivals`` per step) admitted by the
     policy via core.policies.admit_sequential, then placed into free slots

Steps 1–3 are the admission core's ``apply_events``, step 4 its
``decide_batch`` — the step machinery itself lives in ``sim.core`` as pure
functions over one ``CoreState`` pytree (slot table + beliefs + maintained
aggregate curves), shared bit-for-bit with the online serving engine
(``serve.admission``). ``make_run``/``make_fleet_run`` below are thin
``lax.scan`` drivers over that core plus the run-level metric accounting.

Arrival parameters are **pre-drawn outside the scan** so importance sampling
(App. D) can bucket a run by its badness measure before paying for the full
simulation, and so labeled/unlabeled (§7) and pseudo-observation (§6) priors
can be prepared per arrival. The pre-drawn ``ArrivalStream`` is produced by a
pluggable ``ArrivalSource``: ``PriorArrivalSource`` samples the population
priors (the paper's setting), ``traces.replay.TraceArrivalSource`` replays a
recorded ``WorkloadTrace`` — the scan body never knows the difference.

The scan is **blocked by ``agg_refresh_steps``**: cluster-wide aggregate
moment curves (the only thing the admission policies consume) are fully
recomputed once per block — through a fused masked reduction, the per-slot
reference, or the Pallas aggregate kernel (``agg_backend``) — and maintained
incrementally inside the block by folding placed candidates' curves into
the running sums. Per-decision cost is therefore O(grid), independent of the
slot-array size, which is what makes the paper-scale preset feasible on CPU.

**Fleet mode** (paper §2's provider view: dispatch *then* admit): the same
step machinery runs with a leading cluster axis. ``make_fleet_run`` simulates
``FleetConfig.n_clusters`` heterogeneous clusters in one scan — ``CoreState``
and the per-cluster ``RunMetrics`` all carry a leading ``[C]`` axis (the core
functions are vmapped inside the scan body; ``capacity`` becomes the
per-cluster array), and the blocked ``agg_refresh_steps`` refresh runs per
cluster. A pluggable ``sim.routing.Router`` maps each fleet-wide arrival to
a target cluster *before* ``admit_sequential`` runs there (arrivals no
cluster would take are counted as rejected-by-all). A one-cluster fleet
reproduces the single-cluster simulator key-for-key: cluster 0 keeps the
undiverted per-step key chain and the core functions are exactly the
single-cluster code path.
"""
from __future__ import annotations

import collections
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.policies import PolicyParams
# Static configuration, arrival streams, and the admission-core layer all
# live in sim.core; everything historically importable from this module is
# re-exported here (and from sim/__init__) unchanged.
from .core import (AGG_FUSED, AGG_KERNEL, AGG_REFERENCE, GLOBAL, MIX_LABELED,
                   MIX_UNLABELED, PSEUDO, AdmissionCore, ArrivalSource,
                   ArrivalStream, CoreState, FleetConfig, PriorArrivalSource,
                   SimConfig, SimState, StepOutcome, _init_state,
                   _place_arrivals, _step_dynamics, _validate_config,
                   _validate_fleet_config, draw_arrival_stream,
                   make_admission_core, make_config, make_fleet_config,
                   stream_config)

__all__ = [  # noqa: F822 — re-exports keep the historical import surface
    "AGG_FUSED", "AGG_KERNEL", "AGG_REFERENCE", "GLOBAL", "MIX_LABELED",
    "MIX_UNLABELED", "PSEUDO", "AdmissionCore", "ArrivalSource",
    "ArrivalStream", "CoreState", "FleetConfig", "FleetMetrics",
    "PriorArrivalSource", "RunMetrics", "SimConfig", "SimState",
    "StepOutcome", "broadcast_policy", "draw_arrival_stream",
    "make_admission_core", "make_config", "make_fleet_config",
    "make_fleet_run", "make_run", "run_batch", "run_keyed_batch",
    "shard_batch_over_devices", "stream_config",
]


class RunMetrics(NamedTuple):
    utilization: jax.Array        # time-average active cores / capacity
    failure_rate: jax.Array       # failed scale-out requests / total requests
    total_requests: jax.Array
    failed_requests: jax.Array
    arrivals_accepted: jax.Array
    arrivals_rejected: jax.Array
    slot_overflow: jax.Array      # arrivals lost to slot-array exhaustion
    n_departed: jax.Array         # deployments that died (spontaneous or
                                  # core exhaustion) over the whole run
    alive_end: jax.Array          # deployments still alive at the horizon
    util_trace: jax.Array         # [T] active cores after each step
    fail_trace: jax.Array         # [T] failed requests per step


class FleetMetrics(NamedTuple):
    """Fleet-level reductions plus the per-cluster ``RunMetrics``.

    The scalar fields mirror ``RunMetrics`` reduced over the cluster axis
    (capacity-weighted utilization; summed counts) so fleet runs drop into
    any consumer of run-level metrics — ``estimate_from_plan``, the SLA
    aggregation in ``sim.metrics`` — unchanged. ``per_cluster`` carries the
    full ``[C]``-leading per-cluster metrics (``util_trace`` is ``[C, T]``).
    """

    utilization: jax.Array        # total core-hours / (horizon * total capacity)
    failure_rate: jax.Array       # summed failures / summed requests
    total_requests: jax.Array
    failed_requests: jax.Array
    arrivals_accepted: jax.Array
    arrivals_rejected: jax.Array  # per-cluster rejections + rejected_by_all
    rejected_by_all: jax.Array    # arrivals the router could place nowhere
                                  # (threshold-cascade sentinel; 0 for
                                  # single-target routers)
    slot_overflow: jax.Array
    util_trace: jax.Array         # [T] fleet active cores after each step
    fail_trace: jax.Array         # [T] fleet failed requests per step
    per_cluster: RunMetrics       # leading [C] axis on every field


def _run_metrics(cfg: SimConfig, slots: SimState, util_trace, fail_trace,
                 capacity=None, horizon_hours=None) -> RunMetrics:
    """Assemble ``RunMetrics`` from final slot-table accumulators. Shared by
    the offline scan driver and the online engine, so "final metrics" means
    the same arithmetic in both regimes."""
    cap = cfg.capacity if capacity is None else capacity
    horizon = cfg.horizon_hours if horizon_hours is None else horizon_hours
    return RunMetrics(
        utilization=slots.core_hours / (horizon * cap),
        failure_rate=slots.fail_requests
        / jnp.maximum(slots.total_requests, 1.0),
        total_requests=slots.total_requests,
        failed_requests=slots.fail_requests,
        arrivals_accepted=slots.arr_accepted,
        arrivals_rejected=slots.arr_rejected,
        slot_overflow=slots.slot_overflow,
        n_departed=slots.n_departed,
        alive_end=jnp.sum(slots.alive.astype(jnp.float32), axis=-1),
        util_trace=util_trace,
        fail_trace=fail_trace,
    )


def _fleet_metrics(cfg: SimConfig, caps, state: SimState, util_trace,
                   fail_trace, rej_all, horizon_hours=None) -> FleetMetrics:
    """Assemble ``FleetMetrics`` from per-cluster slot-table accumulators
    (leading ``[C]`` axis; ``util_trace``/``fail_trace`` are ``[C, T]``).
    Shared by the offline fleet scan driver and the online engine."""
    horizon = cfg.horizon_hours if horizon_hours is None else horizon_hours
    per_cluster = _run_metrics(cfg, state, util_trace, fail_trace,
                               capacity=caps, horizon_hours=horizon)
    tot_req = jnp.sum(state.total_requests)
    tot_fail = jnp.sum(state.fail_requests)
    return FleetMetrics(
        utilization=jnp.sum(state.core_hours) / (horizon * jnp.sum(caps)),
        failure_rate=tot_fail / jnp.maximum(tot_req, 1.0),
        total_requests=tot_req,
        failed_requests=tot_fail,
        arrivals_accepted=jnp.sum(state.arr_accepted),
        arrivals_rejected=jnp.sum(state.arr_rejected) + rej_all,
        rejected_by_all=rej_all,
        slot_overflow=jnp.sum(state.slot_overflow),
        util_trace=jnp.sum(util_trace, axis=0),
        fail_trace=jnp.sum(fail_trace, axis=0),
        per_cluster=per_cluster,
    )


def _accumulate_step(slots: SimState, out: StepOutcome, n_acc, n_rej,
                     dt: float):
    """Fold one step's outcome into the slot-table metric accumulators;
    returns (slots, util_end). Identical arithmetic for the offline scan and
    the online engine's end-of-step bookkeeping."""
    util_end = jnp.sum(slots.cores * slots.alive.astype(jnp.float32), axis=-1)
    slots = slots._replace(
        core_hours=slots.core_hours + util_end * dt,
        fail_requests=slots.fail_requests + out.failed,
        total_requests=slots.total_requests + out.n_requests,
        arr_accepted=slots.arr_accepted + n_acc,
        arr_rejected=slots.arr_rejected + n_rej,
        n_departed=slots.n_departed + out.departed,
    )
    return slots, util_end


def make_run(cfg: SimConfig, horizon_grid: jax.Array, policy_kind: int,
             arrival_source: ArrivalSource | None = None,
             record_decisions: bool = False):
    """Build the jitted simulator for a fixed policy *kind* (threshold/rho stay
    traced so tuning does not re-jit). Returns run(key, policy) -> RunMetrics.

    ``arrival_source`` selects where arrivals come from (default: sample the
    population priors); an explicit ``stream`` argument to run() still takes
    precedence over the source. With ``record_decisions=True`` the run
    returns ``(RunMetrics, accept [T, A])`` — the per-step admit/reject
    decisions, which is what the online/offline equivalence tests compare.
    With ``cfg.telemetry`` the final ``obs.counters.TelemetryState`` rider is
    appended as one more return element (``(metrics, tel)``, or
    ``(metrics, accept, tel)`` when also recording decisions); decisions and
    metrics are bit-identical with the rider on or off.

    The scan is blocked by ``cfg.agg_refresh_steps`` (= K): the cluster-wide
    aggregate moment curves are fully recomputed from the slot array once per
    block (via ``cfg.agg_backend``), and inside a block the aggregate is
    maintained *incrementally* — each *placed* candidate's curves are folded
    into the running sums, so the per-decision cost is O(grid), independent
    of occupancy. Between refreshes the aggregate is stale by at most K
    steps of within-block dynamics: deaths shrink the true load (stale
    aggregate over-estimates, conservative), while scale-out grants and
    belief updates grow it (stale aggregate under-estimates, optimistic) —
    so K must stay small relative to the scale-out dynamics, and any
    residual bias is absorbed by the SLA-constrained threshold tuning, which
    calibrates against the same simulator at the same K. K = 1 recomputes
    every step (the refresh then lags the seed's in-step recompute by
    exactly the current step's death/belief update).
    """
    core = make_admission_core(cfg, horizon_grid, policy_kind)
    source = PriorArrivalSource() if arrival_source is None else arrival_source
    k_refresh = cfg.agg_refresh_steps
    n_outer = cfg.n_steps // k_refresh

    def step(policy: PolicyParams, cs: CoreState, xs):
        key, stream_t = xs
        cs, out = core.apply_events(key, cs)

        # 4. arrivals, admitted against the maintained aggregate -------------
        valid = jnp.arange(cfg.max_arrivals) < stream_t.n_arrivals
        stream_t, _ = core.lookup(cs, stream_t)
        cand = core.candidates(stream_t)
        cs, accept = core.decide_batch(policy, cs, out.util, cand, stream_t,
                                       valid)

        n_acc = jnp.sum(accept.astype(jnp.float32))
        n_rej = jnp.sum(valid.astype(jnp.float32)) - n_acc
        slots, util_end = _accumulate_step(cs.slots, out, n_acc, n_rej, cfg.dt)
        traces = (util_end, out.failed, accept) if record_decisions \
            else (util_end, out.failed)
        return cs._replace(slots=slots), traces

    def outer_block(policy: PolicyParams, cs: CoreState, xs_block):
        # full refresh of the aggregate from the slot array, once per block
        cs = core.refresh_aggregates(cs)
        return jax.lax.scan(functools.partial(step, policy), cs, xs_block)

    @functools.partial(jax.jit, static_argnames=())
    def run(key: jax.Array, policy: PolicyParams,
            stream: Optional[ArrivalStream] = None):
        k_stream, k_scan = jax.random.split(key)
        if stream is None:
            stream = source.stream(k_stream, cfg)
        keys = jax.random.split(k_scan, cfg.n_steps)
        cs0 = core.init()
        block = lambda x: x.reshape((n_outer, k_refresh) + x.shape[1:])
        xs = jax.tree.map(block, (keys, stream))
        cs, traces = jax.lax.scan(
            functools.partial(outer_block, policy), cs0, xs
        )
        util_trace, fail_trace = traces[0], traces[1]
        metrics = _run_metrics(cfg, cs.slots,
                               util_trace.reshape(cfg.n_steps),
                               fail_trace.reshape(cfg.n_steps))
        out = (metrics,)
        if record_decisions:
            out += (traces[2].reshape(cfg.n_steps, cfg.max_arrivals),)
        if cfg.telemetry:
            out += (cs.tel,)
        return out if len(out) > 1 else metrics

    return run


# ---------------------------------------------------------------------------
# Fleet mode: a leading cluster axis over the same step machinery.
# ---------------------------------------------------------------------------


def _cluster_step_keys(key: jax.Array, n_clusters: int) -> jax.Array:
    """[C] per-cluster event keys for one step.

    Cluster 0 keeps the undiverted per-step key, so a one-cluster fleet
    reproduces ``make_run``'s event randomness key-for-key; clusters 1..C-1
    fold their index in (independent chains, no cross-cluster correlation).
    """
    if n_clusters == 1:
        return key[None]
    return jnp.stack([key] + [jax.random.fold_in(key, c)
                              for c in range(1, n_clusters)])


def _check_fleet_policy_capacity(policy: PolicyParams, fcfg: FleetConfig):
    """Fail fast on a mis-specified fleet policy: each cluster's ``decide``
    admits against ``policy.capacity``, so a scalar fleet-*total* capacity
    tiled to every cluster would let each cluster believe it owns the whole
    fleet's budget — calibration would then return plausible-looking but
    wildly over-optimistic thetas with no error. Skipped when the capacity
    leaf is traced (the values are checked at the first concrete call)."""
    cap = getattr(policy, "capacity", None)
    if cap is None or isinstance(cap, jax.core.Tracer):
        return
    cap = np.asarray(cap)
    target = np.asarray(fcfg.capacities, dtype=np.float64)
    ok = (cap.ndim == 0 or cap.shape == target.shape) and np.allclose(
        np.asarray(cap, np.float64), target, rtol=1e-5)
    if not ok:
        raise ValueError(
            f"policy capacity {cap} does not match FleetConfig.capacities "
            f"{fcfg.capacities}: each cluster admits against its OWN "
            "capacity. Build fleet policies with core.policies.fleet_policy"
            "(kind, capacities=fleet_cfg.capacities, ...); when tuning, pass "
            "such a closure as calibrate(..., policy_fn=...).")


def broadcast_policy(policy: PolicyParams, n_clusters: int) -> PolicyParams:
    """Give every PolicyParams field a leading ``[C]`` cluster axis.

    Scalar fields are tiled; fields already carrying the cluster axis (from
    ``core.policies.fleet_policy``) pass through unchanged. Anything else is
    a shape error — per-cluster parameters must be built deliberately.
    """

    def bc(x):
        x = jnp.asarray(x)
        if x.ndim == 0:
            return jnp.broadcast_to(x, (n_clusters,))
        if x.shape[0] == n_clusters and x.ndim == 1:
            return x
        raise ValueError(
            f"policy field has shape {x.shape}; expected a scalar or a "
            f"[{n_clusters}]-vector (one entry per cluster)")

    return jax.tree.map(bc, policy)


def make_fleet_run(fcfg: FleetConfig, horizon_grid: jax.Array,
                   policy_kind: int, router=None,
                   arrival_source: ArrivalSource | None = None,
                   record_decisions: bool = False):
    """Build the jitted fleet simulator: route, then admit per cluster.

    Returns ``run(key, policy, stream=None) -> FleetMetrics``. ``policy``
    is normally a ``core.policies.fleet_policy`` (``[C]`` fields, per-cluster
    capacities and thresholds); a plain scalar ``PolicyParams`` is tiled to
    every cluster via ``broadcast_policy``, which is only meaningful for a
    homogeneous fleet — ``run`` fails fast when the policy's capacity does
    not match ``FleetConfig.capacities`` per cluster (a tiled fleet-total
    would let every cluster admit against the whole fleet's budget). With
    ``record_decisions=True`` the run returns ``(FleetMetrics,
    accept [T, C, A], assign [T, A])``. With ``fcfg.base.telemetry`` the
    final per-cluster ``TelemetryState`` rider (every leaf ``[C]``-leading;
    ``n_routed`` across clusters is the routing count vector) is appended as
    one more return element.

    Each step: per-cluster dynamics (the core's ``apply_events`` against the
    cluster's own capacity, vmapped over the cluster axis with independent
    key chains), one shared candidate-curve evaluation for the step's
    fleet-wide arrivals, the ``router``'s cluster assignment from the
    per-cluster maintained aggregates, then the core's per-cluster
    ``decide_batch`` (sequential admission + slot placement + incremental
    aggregate fold) on each cluster's assigned arrivals. The blocked
    ``agg_refresh_steps`` refresh recomputes every cluster's aggregate from
    its own slot array once per block. Arrivals the router maps to the
    sentinel ``C`` (the threshold cascade's "no cluster would take it") are
    counted as ``rejected_by_all`` and enter no cluster's admission scan.
    """
    from .routing import LeastUtilizedRouter

    _validate_fleet_config(fcfg)
    cfg = fcfg.base
    core = make_admission_core(cfg, horizon_grid, policy_kind)
    n_c = fcfg.n_clusters
    caps = jnp.asarray(fcfg.capacities, jnp.float32)
    router = LeastUtilizedRouter() if router is None else router
    source = PriorArrivalSource() if arrival_source is None else arrival_source
    k_refresh = cfg.agg_refresh_steps
    n_outer = cfg.n_steps // k_refresh

    def fleet_step(policy: PolicyParams, carry, xs):
        cs, rej_all = carry                          # cs leaves: [C, ...]
        key, stream_t = xs
        keys_c = _cluster_step_keys(key, n_c)
        cs, out = jax.vmap(
            lambda cap, k, cs_c: core.apply_events(k, cs_c, cap))(
                caps, keys_c, cs)

        valid = jnp.arange(cfg.max_arrivals) < stream_t.n_arrivals
        cand = core.candidates(stream_t)

        from .routing import RouteContext

        assign = router.route(
            jax.random.fold_in(key, n_c),
            RouteContext(cand=cand, c0=stream_t.c0, valid=valid,
                         agg_el=cs.agg_el, agg_vl=cs.agg_vl, util=out.util,
                         capacities=caps, policy=policy))
        assign = jnp.clip(assign, 0, n_c)           # sentinel n_c = nowhere
        cluster_mask = valid[None, :] & (
            assign[None, :] == jnp.arange(n_c)[:, None])   # [C, A]
        rej_all = rej_all + jnp.sum(
            (valid & (assign == n_c)).astype(jnp.float32))

        cs, accept = jax.vmap(
            lambda pol_c, cs_c, u_c, valid_c: core.decide_batch(
                pol_c, cs_c, u_c, cand, stream_t, valid_c))(
                    policy, cs, out.util, cluster_mask)

        n_acc = jnp.sum(accept.astype(jnp.float32), axis=1)          # [C]
        n_rej = jnp.sum(cluster_mask.astype(jnp.float32), axis=1) - n_acc
        slots, util_end = _accumulate_step(cs.slots, out, n_acc, n_rej, cfg.dt)
        traces = (util_end, out.failed, accept, assign) if record_decisions \
            else (util_end, out.failed)
        return (cs._replace(slots=slots), rej_all), traces

    def outer_block(policy: PolicyParams, carry, xs_block):
        cs, rej_all = carry
        # full per-cluster refresh of the aggregates, once per block
        cs = jax.vmap(core.refresh_aggregates)(cs)
        return jax.lax.scan(functools.partial(fleet_step, policy),
                            (cs, rej_all), xs_block)

    @functools.partial(jax.jit, static_argnames=())
    def _sim_run(key: jax.Array, policy: PolicyParams,
                 stream: Optional[ArrivalStream] = None):
        policy = broadcast_policy(policy, n_c)
        k_stream, k_scan = jax.random.split(key)
        if stream is None:
            stream = source.stream(k_stream, cfg)
        keys = jax.random.split(k_scan, cfg.n_steps)
        cs0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_c,) + x.shape), core.init())
        block = lambda x: x.reshape((n_outer, k_refresh) + x.shape[1:])
        xs = jax.tree.map(block, (keys, stream))
        (cs, rej_all), traces = jax.lax.scan(
            functools.partial(outer_block, policy),
            (cs0, jnp.zeros(())), xs
        )
        util_trace = traces[0].reshape(cfg.n_steps, n_c).T      # [C, T]
        fail_trace = traces[1].reshape(cfg.n_steps, n_c).T
        metrics = _fleet_metrics(cfg, caps, cs.slots, util_trace, fail_trace,
                                 rej_all)
        out = (metrics,)
        if record_decisions:
            out += (traces[2].reshape(cfg.n_steps, n_c, cfg.max_arrivals),
                    traces[3].reshape(cfg.n_steps, cfg.max_arrivals))
        if cfg.telemetry:
            out += (cs.tel,)
        return out if len(out) > 1 else metrics

    def run(key: jax.Array, policy: PolicyParams,
            stream: Optional[ArrivalStream] = None):
        _check_fleet_policy_capacity(policy, fcfg)
        return _sim_run(key, policy, stream)

    return run


def shard_batch_over_devices(batched, devices, axis: str,
                             n_replicated_args: int = 0,
                             n_batch_args: int = 1):
    """jit(shard_map(batched)) over a 1-d device mesh named ``axis``.

    ``batched`` maps ``n_batch_args`` leading-axis batches (plus
    ``n_replicated_args`` trailing broadcast arguments) to a pytree with the
    same leading axis; the batches are split across devices, replicated args
    go everywhere. The batch size must divide the device count — callers
    with ragged batches pad first (see ``run_keyed_batch``). Shared by
    ``run_batch`` (one batch arg: keys), the trace-ensemble path (two: keys
    + a stream batch), and the importance-sampling probe loop.
    """
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), (axis,))
    in_specs = (P(axis),) * n_batch_args + (P(),) * n_replicated_args
    return jax.jit(jax.shard_map(batched, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(axis), check_vma=False))


# bounded LRU: a weak-keyed cache cannot work here (the cached shard_map
# wrapper closes over run_fn, so the value would pin its own key), and jax's
# jit cache pins run_fn process-wide anyway — so just cap how many compiled
# sharded wrappers we keep across a sweep
_SHARDED_RUN_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_SHARDED_RUN_CACHE_MAX = 8


def _pad_batch(args, n_batch: int, pad: int):
    """Pad the leading axis of the first ``n_batch`` args by repeating their
    last row ``pad`` times (trailing args are replicated, never padded)."""
    if pad == 0:
        return args
    pad_fn = lambda x: jnp.concatenate(
        [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])], axis=0)
    return tuple(jax.tree.map(pad_fn, a) for a in args[:n_batch]) \
        + args[n_batch:]


def run_keyed_batch(run_fn, keys: jax.Array, policy: PolicyParams,
                    *, streams: Optional[ArrivalStream] = None,
                    devices=None) -> RunMetrics:
    """Simulate an explicit ``[R, ...]`` batch of PRNG keys: vmap over runs,
    shard_map over devices.

    With more than one local device the key batch is sharded over a 1-d mesh
    and each device vmaps its shard (pure data parallelism — runs never
    communicate). A batch that does not divide the device count is **padded**
    to the next multiple by repeating its last run (streams ride along), and
    the padded lanes are sliced off before returning — so they never reach a
    caller's metric reductions. Single-device falls back to a plain vmap.
    The compiled sharded wrapper is cached per (run_fn, devices) — the policy
    is a traced argument — so repeated calls do not re-trace.

    Taking keys (not a count) is what lets the importance-sampling estimator
    route its pre-selected ``ImportancePlan.keys`` through the same sharded
    path as ordinary batches (see ``importance.simulate_plan``).

    ``streams`` (optional) is a leading-axis ``[R, ...]`` batch of pre-built
    ``ArrivalStream``\\ s, one per run, sharded alongside the keys — the
    trace-ensemble importance path uses this to pair each selected replay
    stream with its run key (see ``importance.simulate_trace_plan``).
    """
    keys = jnp.asarray(keys)
    n_runs = keys.shape[0]
    devices = tuple(jax.devices() if devices is None else devices)
    n_dev = len(devices)
    if streams is None:
        batched = jax.vmap(run_fn, in_axes=(0, None))
        args = (keys, policy)
        n_batch = 1
    else:
        batched = jax.vmap(lambda k, s, p: run_fn(k, p, s),
                           in_axes=(0, 0, None))
        args = (keys, streams, policy)
        n_batch = 2
    if n_dev <= 1:
        return batched(*args)

    pad = (-n_runs) % n_dev
    args = _pad_batch(args, n_batch, pad)
    cache_key = (run_fn, devices, n_batch)
    sharded = _SHARDED_RUN_CACHE.get(cache_key)
    if sharded is None:
        sharded = shard_batch_over_devices(batched, devices, "runs",
                                           n_replicated_args=1,
                                           n_batch_args=n_batch)
        _SHARDED_RUN_CACHE[cache_key] = sharded
        while len(_SHARDED_RUN_CACHE) > _SHARDED_RUN_CACHE_MAX:
            _SHARDED_RUN_CACHE.popitem(last=False)
    else:
        _SHARDED_RUN_CACHE.move_to_end(cache_key)
    metrics = sharded(*args)
    if pad:
        metrics = jax.tree.map(lambda x: x[:n_runs], metrics)
    return metrics


def run_batch(run_fn, key: jax.Array, policy: PolicyParams, n_runs: int,
              *, devices=None) -> RunMetrics:
    """A batch of ``n_runs`` independent runs split from one key; see
    ``run_keyed_batch`` for the sharding behavior."""
    return run_keyed_batch(run_fn, jax.random.split(key, n_runs), policy,
                           devices=devices)
