"""The admission core: one reusable state + pure-function layer shared by the
offline simulators and the online serving engine.

The paper's provider "has to continuously decide" admission as workloads
arrive — the same decision machinery must therefore run both *offline*
(Monte-Carlo ``lax.scan`` over a pre-drawn horizon, ``sim.simulator``) and
*online* (a long-lived engine answering micro-batched admission requests,
``serve.admission``). This module is that shared layer:

  * ``CoreState`` — the complete admission state as one pytree: the slot
    table with per-deployment conjugate beliefs (``SimState``) plus the
    incrementally-maintained cluster-wide aggregate moment curves.
  * ``make_admission_core(cfg, grid, policy_kind)`` — closes over the static
    configuration and returns an ``AdmissionCore`` bundle of **pure**
    functions over ``CoreState``:

      - ``init()``                      fresh empty state
      - ``refresh_aggregates(cs)``      full aggregate recompute from slots
      - ``apply_events(key, cs)``       one ``dt``-hour step of deaths /
                                        scale-out grants / belief updates
      - ``apply_observed(cs, ev)``      the same step from observed events
      - ``lookup(cs, stream_t)``        arrivals' beliefs from their
                                        tenants' rows (tenant configs)
      - ``candidates(stream_t)``        [A, N] candidate moment curves
      - ``decide_batch(policy, cs, …)`` sequential admission + slot
                                        placement + incremental fold

``sim.simulator.make_run`` / ``make_fleet_run`` are thin scan drivers over
these functions (the fleet vmaps them over a leading cluster axis), and the
online engine calls the same functions one step at a time — which is what
makes online/offline equivalence testable bit-for-bit rather than merely
plausible. Static configuration (``SimConfig``/``FleetConfig``), the
pre-drawn ``ArrivalStream`` and its pluggable ``ArrivalSource`` live here
too so both layers share one vocabulary.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.belief import (GammaBelief, apply_pseudo_observations,
                           belief_from_prior, fold_tenant_arrivals,
                           fold_tenant_events, init_tenant_table,
                           observe_initial_size, tenant_belief,
                           update_on_events)
from ..core.moments import (F32, MomentCurves, aggregate_moment_curves,
                            masked_curve_reduction, moment_curves,
                            moment_curves_fused)
from ..core.policies import (ZEROTH, PolicyParams, admit_sequential,
                             admit_sequential_verbose)
from ..core.pricing import mixture_moments
from ..obs.counters import (TelemetryState, WindowStats, fold_decisions,
                            fold_window, init_telemetry, mark_refresh)
from ..core.processes import (DeploymentParams, PopulationPriors,
                              sample_params, sample_pseudo_observations,
                              sample_step_events)

GLOBAL, PSEUDO, MIX_LABELED, MIX_UNLABELED = "global", "pseudo", "labeled", "unlabeled"
#: Zipf exponent of the tenants' shares of drawn arrivals (an assumption:
#: the head tenant of the Azure trace's 5,958 sends 10.8 %)
TENANT_ZIPF = 1.0
AGG_FUSED, AGG_REFERENCE, AGG_KERNEL = "fused", "reference", "kernel"


class SimConfig(NamedTuple):
    """Static simulation configuration (python values; changing any re-jits)."""

    capacity: float = 2_000.0
    arrival_rate: float = 0.1        # deployments/hour (paper: 1.0 at c=20,000)
    horizon_hours: float = 365 * 24.0
    dt: float = 6.0                  # hours per step
    max_slots: int = 1024
    max_arrivals: int = 4            # cap per step (Poisson tail clipped)
    prior_mode: str = GLOBAL         # GLOBAL | PSEUDO | MIX_LABELED | MIX_UNLABELED
    n_pseudo_obs: int = 0            # paper §6: 0/1/5/50
    d_points: int = 24               # D-term checkpoint count
    use_kernel: bool = False         # Pallas moment_curves kernel (TPU path;
                                     # interpret-mode on CPU, so off by default)
    agg_backend: str = AGG_FUSED     # AGG_FUSED | AGG_REFERENCE | AGG_KERNEL:
                                     # how the cluster-wide aggregate curves
                                     # are computed each step (see make_run)
    agg_refresh_steps: int = 1       # full aggregate recompute every K steps;
                                     # between refreshes admitted candidates'
                                     # curves are folded in incrementally
                                     # (K=1: recompute every step)
    priors: PopulationPriors = None  # population priors; prefer make_config,
                                     # which defaults these to AZURE_PRIORS
    telemetry: bool = False          # carry the obs.counters.TelemetryState
                                     # rider through every step: decision
                                     # reason counters, occupancy/headroom/
                                     # staleness histograms, observables
                                     # sufficient statistics. False (the
                                     # default) compiles the rider out
                                     # entirely — decisions and metrics are
                                     # bit-identical either way
    n_tenants: int = 0               # > 0: every arrival names one of this
                                     # many tenants, and its belief is the
                                     # prior updated by the device-resident
                                     # table of its tenant's pooled history
                                     # (GLOBAL only; 0 compiles it out)

    @property
    def n_steps(self) -> int:
        return int(round(self.horizon_hours / self.dt))


def make_config(**overrides) -> SimConfig:
    """Documented SimConfig constructor: ``priors`` defaults to the fitted
    Azure priors instead of ``None`` and every field is validated eagerly, so
    a bad config fails here rather than deep inside ``belief_from_prior``."""
    if overrides.get("priors") is None:
        from ..core import AZURE_PRIORS

        overrides["priors"] = AZURE_PRIORS
    return _validate_config(SimConfig(**overrides))


def _validate_config(cfg: SimConfig) -> SimConfig:
    if cfg.priors is None:
        raise ValueError(
            "SimConfig.priors is None. Construct configs via "
            "repro.sim.make_config(...) (defaults to AZURE_PRIORS) or pass "
            "priors=<PopulationPriors> explicitly."
        )
    if cfg.prior_mode not in (GLOBAL, PSEUDO, MIX_LABELED, MIX_UNLABELED):
        raise ValueError(f"unknown prior_mode {cfg.prior_mode!r}")
    if cfg.agg_backend not in (AGG_FUSED, AGG_REFERENCE, AGG_KERNEL):
        raise ValueError(f"unknown agg_backend {cfg.agg_backend!r}")
    if cfg.n_tenants < 0:
        raise ValueError(f"n_tenants={cfg.n_tenants} must be >= 0")
    if cfg.n_tenants and cfg.prior_mode != GLOBAL:
        raise ValueError(
            f"n_tenants={cfg.n_tenants} with prior_mode={cfg.prior_mode!r}: "
            "a tenant's history is the information model, starting from "
            "the population prior (prior_mode=GLOBAL)")
    if cfg.n_pseudo_obs < 0:
        raise ValueError(f"n_pseudo_obs={cfg.n_pseudo_obs} must be >= 0")
    if cfg.prior_mode != GLOBAL and cfg.n_pseudo_obs == 0:
        raise ValueError(
            f"prior_mode={cfg.prior_mode!r} with n_pseudo_obs=0 silently "
            "degenerates to GLOBAL (zero pseudo observations leave every "
            "belief — including the §7 mixture components — at the "
            "population prior): use prior_mode=GLOBAL, or set "
            "n_pseudo_obs >= 1"
        )
    if cfg.n_steps <= 0 or cfg.max_slots <= 0 or cfg.max_arrivals <= 0:
        raise ValueError(
            f"degenerate SimConfig: n_steps={cfg.n_steps} "
            f"max_slots={cfg.max_slots} max_arrivals={cfg.max_arrivals}"
        )
    if cfg.agg_refresh_steps < 1 or cfg.n_steps % cfg.agg_refresh_steps:
        raise ValueError(
            f"agg_refresh_steps={cfg.agg_refresh_steps} must be >= 1 and "
            f"divide n_steps={cfg.n_steps}"
        )
    return cfg


class FleetConfig(NamedTuple):
    """Static fleet configuration: a per-cluster ``SimConfig`` template plus
    the per-cluster capacities.

    ``base`` describes each cluster's slot array, step size, information
    model, and aggregate-refresh blocking — *and* the fleet-wide arrival
    process (``arrival_rate``/``max_arrivals`` are the whole fleet's: one
    stream is drawn and routed, not one per cluster). ``base.capacity``
    conventionally holds the fleet total (``make_fleet_config`` sets it);
    the authoritative per-cluster capacities are ``capacities``.
    """

    base: SimConfig
    capacities: tuple                # per-cluster core capacities (static)

    @property
    def n_clusters(self) -> int:
        return len(self.capacities)

    @property
    def total_capacity(self) -> float:
        return float(sum(self.capacities))


def make_fleet_config(capacities, **base_overrides) -> FleetConfig:
    """Documented FleetConfig constructor: ``base_overrides`` build the
    per-cluster template through ``make_config`` (so priors default to
    AZURE_PRIORS and every field is validated); ``base.capacity`` defaults
    to the fleet total."""
    caps = tuple(float(c) for c in capacities)
    base_overrides.setdefault("capacity", sum(caps))
    return _validate_fleet_config(
        FleetConfig(base=make_config(**base_overrides), capacities=caps))


def _validate_fleet_config(fcfg: FleetConfig) -> FleetConfig:
    if not fcfg.capacities:
        raise ValueError("FleetConfig.capacities is empty")
    if any(not np.isfinite(c) or c <= 0.0 for c in fcfg.capacities):
        raise ValueError(
            f"FleetConfig.capacities must be positive, got {fcfg.capacities}")
    _validate_config(fcfg.base)
    if fcfg.base.n_tenants:
        raise ValueError(
            "FleetConfig with n_tenants > 0: the tenant table is not "
            "routed over a fleet's clusters; serve a tenant configuration "
            "as one cluster")
    return fcfg


def stream_config(cfg) -> SimConfig:
    """The ``SimConfig`` governing arrival-stream layout and priors.

    Identity for a plain ``SimConfig``; for a ``FleetConfig`` it is the base
    template with the fleet-total capacity — fleet arrivals are drawn (or
    replayed) fleet-wide and only routed to clusters at simulation time, so
    everything stream-shaped (``draw_arrival_stream``, trace replay, badness
    measures) works on this reduced config.
    """
    if isinstance(cfg, FleetConfig):
        return cfg.base._replace(capacity=cfg.total_capacity)
    return cfg


class ArrivalStream(NamedTuple):
    """Pre-drawn per-(step, arrival-slot) quantities. Leading dims [T, A]."""

    params: DeploymentParams         # true parameters of the arriving deployment
    c0: jax.Array                    # initial request size
    bel: GammaBelief                 # provider's prior belief for the arrival
    bel_alt: GammaBelief             # second mixture component (unlabeled mode)
    n_arrivals: jax.Array            # [T] arrivals per step (already capped)
    tenant: Optional[jax.Array] = None   # [T, A] int32 tenant ids, with
                                         # cfg.n_tenants; None otherwise


class ArrivalSource:
    """Pluggable producer of the pre-drawn ``ArrivalStream``.

    ``make_run`` consumes arrivals exclusively through this interface: the
    scan body, policies, and importance sampling only ever see the stream,
    so any source that returns correctly-shaped ``[n_steps, max_arrivals]``
    fields plugs in without touching the simulator. Two backends ship:
    ``PriorArrivalSource`` (sample the population priors — the seed
    behavior) and ``traces.replay.TraceArrivalSource`` (replay a recorded
    ``WorkloadTrace``). ``stream`` is called inside the jitted run, so it
    must be traceable; closed-over trace arrays become constants.
    """

    def stream(self, key: jax.Array, cfg: SimConfig) -> "ArrivalStream":
        raise NotImplementedError


class PriorArrivalSource(ArrivalSource):
    """Draw every arrival from the population priors (paper §5 default)."""

    def stream(self, key: jax.Array, cfg: SimConfig) -> "ArrivalStream":
        return draw_arrival_stream(key, cfg)


def draw_arrival_stream(key: jax.Array, cfg: SimConfig) -> ArrivalStream:
    """Pre-draw every arrival's true params, request size and prior belief."""
    cfg = stream_config(cfg)
    t_steps, a_max = cfg.n_steps, cfg.max_arrivals
    shape = (t_steps, a_max)
    kn, kp, kc, ko, kq, kb = jax.random.split(key, 6)
    n_arr = jnp.minimum(
        jax.random.poisson(kn, cfg.arrival_rate * cfg.dt, (t_steps,)), a_max
    )
    tenant = None
    if cfg.n_tenants:
        # every deployment of a tenant shares one scaling type, drawn once
        # per tenant; tenants' shares of arrivals fall off as 1/rank^s
        kt, ki = jax.random.split(kp)
        types = sample_params(kt, cfg.priors, (cfg.n_tenants,))
        rank = jnp.arange(1, cfg.n_tenants + 1, dtype=jnp.float32)
        tenant = jax.random.categorical(
            ki, -TENANT_ZIPF * jnp.log(rank), shape=shape)
        params = jax.tree.map(lambda x: x[tenant], types)
    else:
        params = sample_params(kp, cfg.priors, shape)
    c0 = (1 + jax.random.poisson(kc, params.sig)).astype(jnp.float32)

    prior = belief_from_prior(cfg.priors, shape)
    if cfg.prior_mode == GLOBAL:
        bel = prior
        bel_alt = bel
    elif cfg.prior_mode == PSEUDO:
        obs = sample_pseudo_observations(ko, params, cfg.priors, cfg.n_pseudo_obs)
        bel = apply_pseudo_observations(prior, obs, cfg.priors)
        bel_alt = bel
    else:
        # §7: the user has two types; the submitted deployment is the drawn
        # ``params``; the alternative type is an independent draw. The provider
        # holds n_pseudo_obs observations of each type.
        alt = sample_params(kq, cfg.priors, shape)
        k1, k2 = jax.random.split(kb)
        obs = sample_pseudo_observations(k1, params, cfg.priors, cfg.n_pseudo_obs)
        obs_alt = sample_pseudo_observations(k2, alt, cfg.priors, cfg.n_pseudo_obs)
        bel = apply_pseudo_observations(prior, obs, cfg.priors)
        bel_alt = apply_pseudo_observations(prior, obs_alt, cfg.priors)
    bel = observe_initial_size(bel, c0)
    return ArrivalStream(params=params, c0=c0, bel=bel, bel_alt=bel_alt,
                         n_arrivals=n_arr, tenant=tenant)


class SimState(NamedTuple):
    """Slot table (fixed-capacity deployment array + conjugate beliefs) plus
    the run-level metric accumulators."""

    alive: jax.Array              # [S] bool
    cores: jax.Array              # [S] float32
    params: DeploymentParams      # [S]
    bel: GammaBelief              # [S]
    core_hours: jax.Array
    fail_requests: jax.Array
    total_requests: jax.Array
    arr_accepted: jax.Array
    arr_rejected: jax.Array
    slot_overflow: jax.Array
    n_departed: jax.Array
    tenant: Optional[jax.Array] = None   # [S] int32 tenant of each slot's
                                         # deployment, with cfg.n_tenants


class CoreState(NamedTuple):
    """The complete admission state: slot table + beliefs (``slots``) and the
    incrementally-maintained cluster-wide aggregate moment curves. One
    pytree, so a long-lived engine can keep it device-resident and donate it
    through every jitted step (the fleet gives every leaf a leading ``[C]``
    cluster axis).

    ``tel`` is the optional telemetry rider (``obs.counters.TelemetryState``):
    ``None`` — an empty pytree node, adding no buffers to the compiled
    programs — unless ``SimConfig(telemetry=True)``. ``tenants`` is the
    ``[2, n_tenants, len(TENANT_COLUMNS)]`` compensated float32 tenant
    table (``core.belief``), likewise ``None`` unless ``SimConfig.n_tenants``."""

    slots: SimState
    agg_el: jax.Array             # [N] aggregate E[L_n] over admitted slots
    agg_vl: jax.Array             # [N] aggregate V[L_n]
    tel: Optional["TelemetryState"] = None
    tenants: Optional[jax.Array] = None


class StepOutcome(NamedTuple):
    """Per-step dynamics summary from ``apply_events`` (metric inputs)."""

    util: jax.Array               # active cores after deaths + grants
    failed: jax.Array             # scale-out requests that did not fit
    n_requests: jax.Array         # total scale-out requests this step
    departed: jax.Array           # deployments that died this step


def _init_state(cfg: SimConfig) -> SimState:
    s = cfg.max_slots
    # explicit dtype => strong-typed f32: the online engine re-feeds this
    # state through jit, and a weak-typed leaf would flip to strong on the
    # first slot placement and force a full recompile of every step fn
    zero_params = DeploymentParams(
        lam=jnp.zeros(s), mu=jnp.full((s,), 1.0, jnp.float32),
        sig=jnp.zeros(s)
    )
    return SimState(
        alive=jnp.zeros(s, bool),
        cores=jnp.zeros(s, jnp.float32),
        params=zero_params,
        bel=belief_from_prior(cfg.priors, (s,)),
        core_hours=jnp.zeros(()),
        fail_requests=jnp.zeros(()),
        total_requests=jnp.zeros(()),
        arr_accepted=jnp.zeros(()),
        arr_rejected=jnp.zeros(()),
        slot_overflow=jnp.zeros(()),
        n_departed=jnp.zeros(()),
        tenant=jnp.zeros(s, jnp.int32) if cfg.n_tenants else None,
    )


def _place_arrivals(state: SimState, accept, stream_t: ArrivalStream, cfg: SimConfig):
    """Place accepted arrivals into free slots, one vectorized pass.

    The i-th accepted arrival goes to the i-th free slot (in slot order) —
    identical semantics to the previous sequential argmin unroll, but a single
    [A, S] rank-match instead of A passes over the slot array. Accepted
    arrivals beyond the number of free slots are counted as slot overflow.

    Each slot leaf takes its new values by a one-hot select over the arrival
    axis of the match ``hit`` [A, S], with no gather indexed by slot: no two
    arrivals hit one slot, so OR-folding the selected bit patterns over the
    arrivals yields the one hit's value exactly (NaN, inf and -0.0 included).
    Under the fleet's vmap a per-slot gather from the small arrival table
    costs far more than this elementwise [A, S] pass.

    Returns (state, placed_arrival [A]) — the mask of accepted arrivals that
    actually landed in a slot, so the caller folds only *real* deployments
    into the maintained aggregate (overflowed arrivals must not haunt it).
    """
    alive = state.alive
    free = ~alive
    rank = jnp.cumsum(free.astype(jnp.int32))          # free-slot rank, 1-based
    acc = accept.astype(jnp.int32)
    ordinal = jnp.cumsum(acc) * acc                    # i-th accepted, 1-based
    n_free = rank[-1]
    placed_arrival = accept & (ordinal <= n_free)      # [A]
    overflow = state.slot_overflow + jnp.sum(
        jnp.where(accept & ~placed_arrival, 1.0, 0.0))

    hit = free[None, :] & (rank[None, :] == ordinal[:, None]) & accept[:, None]
    placed = jnp.any(hit, axis=0)                      # [S]

    def merge(old, new_a):
        bits_t = jnp.dtype(f"uint{8 * new_a.dtype.itemsize}")
        bits = jax.lax.bitcast_convert_type(new_a, bits_t)
        picked = jnp.bitwise_or.reduce(
            jnp.where(hit, bits[:, None], bits_t.type(0)), axis=0)
        return jnp.where(placed,
                         jax.lax.bitcast_convert_type(picked, new_a.dtype), old)

    cores = merge(state.cores, stream_t.c0)
    params = jax.tree.map(lambda o, n: merge(o, n), state.params,
                          stream_t.params)
    bel = jax.tree.map(lambda o, n: merge(o, n), state.bel, stream_t.bel)
    state = state._replace(alive=alive | placed, cores=cores, params=params,
                           bel=bel, slot_overflow=overflow)
    if cfg.n_tenants:
        state = state._replace(tenant=merge(state.tenant, stream_t.tenant))
    return state, placed_arrival


def _make_aggregate_fn(cfg: SimConfig, grid: jax.Array):
    """Cluster-wide sum-over-alive-slots curve evaluator, by backend.

    AGG_REFERENCE is the seed per-slot path (materialize [S, N], mask, sum) —
    kept as the oracle the fast paths are equivalence-tested against.
    AGG_FUSED reduces block-by-block without the [S, N] intermediate;
    AGG_KERNEL is the Pallas aggregated-output kernel (interpret-mode on CPU).
    """
    if cfg.agg_backend == AGG_REFERENCE:

        def aggregate(bel, cores, alive):
            curves = moment_curves(bel, cores, grid, cfg.priors,
                                   d_points=cfg.d_points)
            alive_f = alive.astype(jnp.float32)
            return (jnp.sum(curves.EL * alive_f[:, None], axis=0),
                    jnp.sum(curves.VL * alive_f[:, None], axis=0))
    elif cfg.agg_backend == AGG_KERNEL:
        from ..kernels.moment_curves.ops import aggregate_moment_curves_kernel

        def aggregate(bel, cores, alive):
            out = aggregate_moment_curves_kernel(
                bel, cores, alive, grid, cfg.priors, d_points=cfg.d_points)
            return out.EL, out.VL
    else:

        def aggregate(bel, cores, alive):
            out = aggregate_moment_curves(bel, cores, alive, grid, cfg.priors,
                                          d_points=cfg.d_points)
            return out.EL, out.VL

    return aggregate


def _make_curves_fn(cfg: SimConfig):
    """Per-candidate moment-curve evaluator (fused jnp or Pallas kernel)."""
    if cfg.use_kernel:
        from ..kernels.moment_curves.ops import moment_curves_kernel

        def curves_fn(bel, cores, grid_, priors, d_points):
            flat_bel = jax.tree.map(lambda a: a.reshape(-1), bel)
            out = moment_curves_kernel(flat_bel, cores.reshape(-1), grid_,
                                       priors, d_points=d_points)
            shape = cores.shape + (grid_.shape[0],)
            return MomentCurves(out.EL.reshape(shape), out.VL.reshape(shape))

        return curves_fn
    return moment_curves_fused


def _make_candidates_fn(cfg: SimConfig, grid: jax.Array, needs_moments: bool,
                        n_grid: int, curves_fn):
    """[A, N] candidate curves for one step's pre-drawn arrivals (mixture
    moments in the §7 unlabeled mode; zeros when the policy ignores them)."""

    def candidates(stream_t: ArrivalStream) -> MomentCurves:
        if not needs_moments:
            return MomentCurves(EL=jnp.zeros((stream_t.c0.shape[0], n_grid)),
                                VL=jnp.zeros((stream_t.c0.shape[0], n_grid)))
        cand = curves_fn(stream_t.bel, stream_t.c0, grid, cfg.priors,
                         d_points=cfg.d_points)
        if cfg.prior_mode == MIX_UNLABELED:
            cand_alt = curves_fn(stream_t.bel_alt, stream_t.c0, grid,
                                 cfg.priors, d_points=cfg.d_points)
            stacked = MomentCurves(
                EL=jnp.stack([cand.EL, cand_alt.EL]),
                VL=jnp.stack([cand.VL, cand_alt.VL]),
            )
            cand = mixture_moments(jnp.asarray([0.5, 0.5]), stacked)
        return cand

    return candidates


def _step_dynamics(cfg: SimConfig, capacity, key, state: SimState,
                   with_stats: bool = False, tenants=None):
    """Steps 1–3 of one ``dt``-hour step for ONE cluster, with the window's
    events drawn from the deployments' true processes under ``key``; the
    rest is ``_apply_window``'s."""
    ev = sample_step_events(key, state.params, state.cores, cfg.priors,
                            cfg.dt, alive=state.alive)
    return _apply_window(cfg, capacity, state, ev, with_stats, tenants)


def _apply_window(cfg: SimConfig, capacity, state: SimState, ev,
                  with_stats: bool = False, tenants=None):
    """Deaths, scale-out grants against ``capacity`` (a traced value — the
    fleet passes each cluster's own) and conjugate belief updates of one
    window's events ``ev`` (``core_deaths``, ``spont_death``,
    ``scaleout_cores``, ``n_scaleouts`` per slot: drawn, or observed), and
    with a tenant table the same observations folded into each slot's
    tenant row.

    Returns ``(state, tenants, util, failed, n_req_total, departed,
    stats)`` with the slot arrays updated and the metric counters untouched
    (the caller accumulates them after admission). ``stats`` is the
    window's observable sufficient statistics (``WindowStats``) when
    ``with_stats`` — the telemetry rider's drift-detector stream — else
    ``None``.
    """
    alive_f = state.alive.astype(jnp.float32)

    # 1. deaths ---------------------------------------------------------
    deaths = jnp.minimum(ev.core_deaths.astype(jnp.float32), state.cores) * alive_f
    exposure = state.cores * cfg.dt * alive_f
    cores = state.cores - deaths
    cores = jnp.where(ev.spont_death & state.alive, 0.0, cores)
    alive = state.alive & (cores > 0.0)
    departed = jnp.sum((state.alive & ~alive).astype(jnp.float32))
    spont = jnp.sum((ev.spont_death & state.alive).astype(jnp.float32))
    alive_f = alive.astype(jnp.float32)

    # 2. scale-outs (only deployments still alive request) ---------------
    req = ev.scaleout_cores.astype(jnp.float32) * alive_f
    n_req = ev.n_scaleouts.astype(jnp.float32) * alive_f
    util = jnp.sum(cores * alive_f)
    grant = (util + jnp.cumsum(req)) <= capacity
    cores = cores + jnp.where(grant, req, 0.0)
    failed = jnp.sum(jnp.where(~grant, n_req, 0.0))
    util = jnp.sum(cores * alive_f)

    # 3. belief updates (requests are observed whether or not granted) ---
    observed = dict(core_deaths=deaths, exposure_core_hours=exposure,
                    n_scaleouts=n_req, scaleout_cores=req,
                    alive_hours=cfg.dt * alive_f)
    bel = update_on_events(state.bel, priors=cfg.priors, **observed)
    if tenants is not None:
        with jax.named_scope("tenant_fold"):
            tenants = fold_tenant_events(tenants, state.tenant, **observed)
    state = state._replace(alive=alive, cores=cores, bel=bel)
    stats = None
    if with_stats:
        stats = WindowStats(
            core_deaths=jnp.sum(deaths),
            exposure_core_hours=jnp.sum(exposure),
            n_scaleouts=jnp.sum(n_req),
            scaleout_cores=jnp.sum(req),
            alive_hours=cfg.dt * jnp.sum(alive_f),
            spont_deaths=spont,
            departed=departed,
        )
    return state, tenants, util, failed, jnp.sum(n_req), departed, stats


def slot_mesh(n_shards: int, devices=None):
    """A 1-d device mesh named ``"slots"`` over the first ``n_shards``
    devices — the mesh ``make_admission_core(..., mesh=...)`` shards the
    slot axis of ``CoreState`` over. Raises with guidance when the process
    has too few devices (CPU runs get more via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    if n_shards < 1:
        raise ValueError(f"n_shards={n_shards} must be >= 1")
    if n_shards > len(devices):
        raise ValueError(
            f"n_shards={n_shards} exceeds the {len(devices)} visible "
            "device(s); on CPU, export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards}")
    return Mesh(np.asarray(devices[:n_shards]), ("slots",))


class AdmissionCore(NamedTuple):
    """Bundle of pure functions over ``CoreState`` for one static
    configuration (see module docstring). Built by ``make_admission_core``;
    every field closing over ``cfg``/``grid``/``policy_kind`` so callers jit,
    vmap, or scan them freely."""

    cfg: SimConfig
    grid: jax.Array
    policy_kind: int
    needs_moments: bool
    n_grid: int
    init: Callable[[], CoreState]
    refresh_aggregates: Callable[[CoreState], CoreState]
    apply_events: Callable[..., tuple]
    apply_observed: Callable[..., tuple]
    lookup: Callable[..., tuple]
    candidates: Callable[[ArrivalStream], MomentCurves]
    decide_batch: Callable[..., tuple]
    decide_batch_traced: Callable[..., tuple]


def make_admission_core(cfg: SimConfig, grid: jax.Array,
                        policy_kind: int, *, mesh=None) -> AdmissionCore:
    """Build the pure admission-core function bundle for one configuration.

    All its functions are pure pytree -> pytree maps (no python state), so
    the offline drivers scan them, the fleet vmaps them over the cluster
    axis, and the online engine jits them individually with donated
    ``CoreState`` buffers — one implementation, three execution regimes.

    ``mesh`` (optional, a 1-d ``jax.sharding.Mesh`` — see ``slot_mesh``)
    selects the **device-sharded lane**: ``CoreState``'s slot axis is
    partitioned over the mesh so one engine's state scales with device
    count, and ``refresh_aggregates`` evaluates each shard's per-slot moment
    curves locally before reducing them in the unsharded path's exact block
    order — decisions and metrics stay bit-for-bit identical to the
    single-device core (see ``_shard_over_slots``). ``mesh=None`` (the
    default) is exactly the historical single-device core.
    """
    _validate_config(cfg)
    needs_moments = policy_kind != ZEROTH
    n_grid = grid.shape[0] if needs_moments else 1
    curves_fn = _make_curves_fn(cfg)
    aggregate_fn = _make_aggregate_fn(cfg, grid)
    candidates_fn = _make_candidates_fn(cfg, grid, needs_moments, n_grid,
                                        curves_fn)

    def init() -> CoreState:
        tenants = (init_tenant_table(cfg.n_tenants) if cfg.n_tenants
                   else None)
        return CoreState(slots=_init_state(cfg),
                         agg_el=jnp.zeros((n_grid,)),
                         agg_vl=jnp.zeros((n_grid,)),
                         tel=init_telemetry() if cfg.telemetry else None,
                         tenants=tenants)

    def refresh_aggregates(cs: CoreState) -> CoreState:
        """Full aggregate recompute from the slot table (block boundary).
        Zeroth-moment policies never read the curves, so their refresh
        keeps the zero placeholder instead of paying for the reduction.
        With telemetry the rider's staleness clock returns to zero."""
        tel = mark_refresh(cs.tel) if cfg.telemetry else cs.tel
        if not needs_moments:
            return cs._replace(agg_el=jnp.zeros((n_grid,)),
                               agg_vl=jnp.zeros((n_grid,)), tel=tel)
        agg_el, agg_vl = aggregate_fn(cs.slots.bel, cs.slots.cores,
                                      cs.slots.alive)
        return cs._replace(agg_el=agg_el, agg_vl=agg_vl, tel=tel)

    def _stepped(cs, cap, window):
        slots, tenants, util, failed, n_req, departed, stats = window
        tel = cs.tel
        if cfg.telemetry:
            tel = fold_window(tel, util, cap, stats)
        return cs._replace(slots=slots, tel=tel, tenants=tenants), \
            StepOutcome(util=util, failed=failed, n_requests=n_req,
                        departed=departed)

    def apply_events(key: jax.Array, cs: CoreState, capacity=None):
        """One ``dt``-hour step of cluster dynamics: deaths, scale-out
        grants against ``capacity`` (defaults to the config's own; the
        fleet passes each cluster's), and conjugate belief updates. The
        maintained aggregate is NOT touched — within-block staleness is the
        ``agg_refresh_steps`` contract. With telemetry the rider folds the
        window's occupancy and observable sufficient statistics; with
        tenants the table folds each slot's observations."""
        cap = cfg.capacity if capacity is None else capacity
        return _stepped(cs, cap, _step_dynamics(
            cfg, cap, key, cs.slots, cfg.telemetry, cs.tenants))

    def apply_observed(cs: CoreState, ev, capacity=None):
        """``apply_events`` with the window's events observed (an
        ``ExternalEvents``-shaped ``ev``) instead of drawn."""
        cap = cfg.capacity if capacity is None else capacity
        return _stepped(cs, cap, _apply_window(
            cfg, cap, cs.slots, ev, cfg.telemetry, cs.tenants))

    def lookup(cs: CoreState, stream_t: ArrivalStream):
        """``(stream_t, informed)``: with tenants, each arrival's belief
        formed from its tenant's row as the table stands
        (``core.belief.tenant_belief``) and where that row held an admitted
        deployment; without, the stream as it is and ``None``."""
        if not cfg.n_tenants:
            return stream_t, None
        with jax.named_scope("tenant_lookup"):
            bel, informed = tenant_belief(cs.tenants, stream_t.tenant,
                                          stream_t.c0, cfg.priors)
        return stream_t._replace(bel=bel, bel_alt=bel), informed

    def _decide_core(policy: PolicyParams, cs: CoreState, util,
                     cand: MomentCurves, stream_t: ArrivalStream, valid,
                     verbose: bool):
        if verbose or cfg.telemetry:
            res, diag = admit_sequential_verbose(
                policy, cs.agg_el, cs.agg_vl, util, cand, stream_t.c0, valid)
        else:
            res = admit_sequential(policy, cs.agg_el, cs.agg_vl, util, cand,
                                   stream_t.c0, valid)
            diag = None
        slots, placed_arrival = _place_arrivals(cs.slots, res.accept,
                                                stream_t, cfg)
        placed_f = placed_arrival.astype(jnp.float32)
        agg_el = cs.agg_el + jnp.einsum("an,a->n", cand.EL, placed_f,
                                        precision=F32)
        agg_vl = cs.agg_vl + jnp.einsum("an,a->n", cand.VL, placed_f,
                                        precision=F32)
        tel = cs.tel
        if cfg.telemetry:
            tel = fold_decisions(tel, res.accept, valid, diag.fits,
                                 placed_arrival, stream_t.c0)
        tenants = cs.tenants
        if cfg.n_tenants:
            with jax.named_scope("tenant_fold"):
                tenants = fold_tenant_arrivals(tenants, stream_t.tenant,
                                               stream_t.c0, placed_arrival)
        return CoreState(slots=slots, agg_el=agg_el, agg_vl=agg_vl,
                         tel=tel, tenants=tenants), res.accept, diag

    def decide_batch(policy: PolicyParams, cs: CoreState, util,
                     cand: MomentCurves, stream_t: ArrivalStream, valid):
        """Greedy first-come-first-served admission of a candidate batch
        against the maintained aggregate (sequential, paper Assumption 3),
        slot placement, and the incremental aggregate fold of *placed*
        arrivals — accepted-but-overflowed ones never became deployments,
        so they must not haunt the carried aggregate. Returns
        (cs, accept [A]). With telemetry the rider folds the batch's reason
        counters and the admitted-arrival stream moments; with tenants the
        placed arrivals' sizes fold into their tenants' rows."""
        cs, accept, _ = _decide_core(policy, cs, util, cand, stream_t, valid,
                                     verbose=False)
        return cs, accept

    def decide_batch_traced(policy: PolicyParams, cs: CoreState, util,
                            cand: MomentCurves, stream_t: ArrivalStream,
                            valid):
        """``decide_batch`` + the per-candidate ``DecisionDiag`` (``[A]``:
        fit flag, policy score, bound) for decision tracing. Returns
        (cs, accept, diag); decisions identical to ``decide_batch``."""
        return _decide_core(policy, cs, util, cand, stream_t, valid,
                            verbose=True)

    core = AdmissionCore(cfg=cfg, grid=grid, policy_kind=policy_kind,
                         needs_moments=needs_moments, n_grid=n_grid,
                         init=init, refresh_aggregates=refresh_aggregates,
                         apply_events=apply_events,
                         apply_observed=apply_observed, lookup=lookup,
                         candidates=candidates_fn,
                         decide_batch=decide_batch,
                         decide_batch_traced=decide_batch_traced)
    if mesh is None:
        return core
    return _shard_over_slots(core, mesh)


def _shard_over_slots(core: AdmissionCore, mesh) -> AdmissionCore:
    """Wrap an ``AdmissionCore`` so ``CoreState``'s slot axis is sharded
    over ``mesh`` (one named axis), keeping decisions and metrics
    **bit-for-bit identical** to the unsharded core.

    What is sharded vs replicated, and why equality holds exactly:

      * The slot table and per-deployment beliefs (every ``[S]`` leaf of
        ``SimState``) live partitioned, ``S / n_shards`` slots per device —
        the state whose size the ROADMAP wants to scale with device count.
      * ``refresh_aggregates`` — the engine's dominant O(S·N) cost —
        evaluates each shard's per-slot moment curves locally, all-gathers
        the (elementwise, hence bitwise-identical) ``[S, N]`` curve values,
        and reduces them via ``masked_curve_reduction``, which replays the
        unsharded fused path's exact einsum/block-fold order. A per-shard
        partial-sum + tree-reduce would NOT be bitwise equal (float sums
        are order-sensitive); gathering the curves and reducing in the
        canonical order is what buys exact equality.
      * Per-step dynamics and admission (O(S) / O(A·N) — cheap next to the
        refresh) run replicated on the gathered slot table and re-slice the
        updated ``[S]`` leaves back to the local shard: every device runs
        the same ops on the same data (including the step's random event
        draws from the replicated key, which keeps global-shape threefry
        semantics), so the replicated outputs are identical by
        construction. ``check_vma`` stays off accordingly.
      * Scalar accumulators, aggregate curves, the telemetry rider, policy
        parameters and arrival batches are replicated (``P()``).

    Donation still works: the engine's ``jit(..., donate_argnums=...)``
    wraps these shard_mapped functions and the sharded-in/sharded-out
    specs let XLA reuse the slot-table buffers in place.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg, grid = core.cfg, core.grid
    if len(mesh.axis_names) != 1:
        raise ValueError(f"mesh must have exactly one axis, got "
                         f"{mesh.axis_names}")
    ax = mesh.axis_names[0]
    n_shards = int(mesh.devices.size)
    if cfg.max_slots % n_shards:
        raise ValueError(
            f"max_slots={cfg.max_slots} must be divisible by the "
            f"{n_shards}-device mesh")
    if cfg.n_tenants:
        raise ValueError(
            "sharded admission core with n_tenants > 0: the tenant table "
            "is not sharded; serve a tenant configuration unsharded")
    if cfg.agg_backend != AGG_FUSED:
        raise ValueError(
            f"sharded admission core requires agg_backend={AGG_FUSED!r} "
            f"(got {cfg.agg_backend!r}): the sharded refresh mirrors the "
            "fused block reduction bit-for-bit")
    s_local = cfg.max_slots // n_shards

    rep = lambda tree: jax.tree.map(lambda _: P(), tree)
    cs_t = jax.eval_shape(core.init)
    cs_specs = CoreState(
        slots=cs_t.slots._replace(
            alive=P(ax), cores=P(ax),
            params=jax.tree.map(lambda _: P(ax), cs_t.slots.params),
            bel=jax.tree.map(lambda _: P(ax), cs_t.slots.bel),
            core_hours=P(), fail_requests=P(), total_requests=P(),
            arr_accepted=P(), arr_rejected=P(), slot_overflow=P(),
            n_departed=P()),
        agg_el=P(), agg_vl=P(),
        tel=rep(cs_t.tel) if cs_t.tel is not None else None)

    gather = lambda x: jax.lax.all_gather(x, ax, axis=0, tiled=True)

    def gather_slots(slots: SimState) -> SimState:
        return jax.tree.map(lambda x: gather(x) if x.ndim else x, slots)

    def slice_slots(slots: SimState) -> SimState:
        i = jax.lax.axis_index(ax)
        loc = lambda x: jax.lax.dynamic_slice_in_dim(x, i * s_local,
                                                     s_local, axis=0)
        return jax.tree.map(lambda x: loc(x) if x.ndim else x, slots)

    def sharded_init() -> CoreState:
        cs = core.init()
        shardings = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                                 cs_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(cs, shardings)

    def _local_refresh(cs: CoreState) -> CoreState:
        tel = mark_refresh(cs.tel) if cfg.telemetry else cs.tel
        if not core.needs_moments:
            return cs._replace(agg_el=jnp.zeros((core.n_grid,)),
                               agg_vl=jnp.zeros((core.n_grid,)), tel=tel)
        # the O(S*N) per-slot curve math runs on the local shard only; the
        # gathered curves are then reduced in the canonical block order
        cur = moment_curves_fused(cs.slots.bel, cs.slots.cores, grid,
                                  cfg.priors, d_points=cfg.d_points)
        mask = cs.slots.alive.astype(grid.dtype)
        agg = masked_curve_reduction(jax.tree.map(gather, cur), gather(mask))
        return cs._replace(agg_el=agg.EL, agg_vl=agg.VL, tel=tel)

    sm_refresh = jax.shard_map(_local_refresh, mesh=mesh,
                               in_specs=(cs_specs,), out_specs=cs_specs,
                               check_vma=False)

    def _local_apply(key, cs: CoreState, capacity):
        full, out = core.apply_events(
            key, cs._replace(slots=gather_slots(cs.slots)), capacity)
        return full._replace(slots=slice_slots(full.slots)), out

    sm_apply = jax.shard_map(
        _local_apply, mesh=mesh, in_specs=(P(), cs_specs, P()),
        out_specs=(cs_specs, P()), check_vma=False)

    def sharded_apply(key, cs: CoreState, capacity=None):
        cap = jnp.asarray(cfg.capacity if capacity is None else capacity,
                          jnp.float32)
        return sm_apply(key, cs, cap)

    def _local_decide(policy, cs, util, cand, stream_t, valid):
        full, accept = core.decide_batch(
            policy, cs._replace(slots=gather_slots(cs.slots)), util, cand,
            stream_t, valid)
        return full._replace(slots=slice_slots(full.slots)), accept

    sm_decide = jax.shard_map(
        _local_decide, mesh=mesh,
        in_specs=(P(), cs_specs, P(), P(), P(), P()),
        out_specs=(cs_specs, P()), check_vma=False)

    def _local_decide_traced(policy, cs, util, cand, stream_t, valid):
        full, accept, diag = core.decide_batch_traced(
            policy, cs._replace(slots=gather_slots(cs.slots)), util, cand,
            stream_t, valid)
        return full._replace(slots=slice_slots(full.slots)), accept, diag

    sm_decide_traced = jax.shard_map(
        _local_decide_traced, mesh=mesh,
        in_specs=(P(), cs_specs, P(), P(), P(), P()),
        out_specs=(cs_specs, P(), P()), check_vma=False)

    return core._replace(init=sharded_init, refresh_aggregates=sm_refresh,
                         apply_events=sharded_apply, decide_batch=sm_decide,
                         decide_batch_traced=sm_decide_traced)
