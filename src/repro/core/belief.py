"""Conjugate Gamma belief state over deployment scaling processes (paper §2.2).

The provider cannot observe (lam, mu, sig); it maintains, per deployment slot,
Gamma posteriors that start at the population prior and are updated from the
observable events (core deaths + exposure, scale-out counts, scale-out sizes):

  * mu  | data ~ Gamma(a  + #deaths,      b  + total core-hours observed)
        (exponential lifetimes, right-censored cores contribute exposure only)
  * sig | data ~ Gamma(as + sum(size-1),  bs + #size observations)
        (size - 1 ~ Poisson(sig); the arrival size C0 counts as one observation)
  * lam | data ~ Gamma(al + #scale-outs,  bl + E[mu**nu] * alive-hours)
        (scale-outs ~ Poisson(lam * mu**nu * t); mu is latent, so the exposure
        uses the posterior mean of mu**nu — an E-step approximation, documented
        in DESIGN.md §4. This keeps the update conjugate and O(1).)

All fields are arrays over deployment slots so the whole belief state is a jit
friendly pytree.

Every Gamma-function ratio Gamma(a+p)/Gamma(a) here and in ``core.moments``
goes through ``log_gamma_ratio``, which never subtracts two large
log-gammas: the shape ``a`` grows with every observed death, and a tenant's
pooled history (``tenant_belief``) takes it to 1e4-1e5, where
``gammaln(a+p) - gammaln(a)`` in float32 loses the ratio to rounding.

A tenant table (``[2, T, TENANT_COLUMNS]``) pools, per tenant, the
sufficient statistics of every deployment that tenant has had admitted, as
compensated float32 sums: ``table[0]`` the sum and ``table[1]`` what
rounding left out of it, so a column keeps counting exactly long after its
sum passes 2**24 (``add_to_tenant_table``). Under the model in which all
of a tenant's deployments share one scaling type, that
history is a set of paper-§6 pseudo observations of the next deployment,
so the provider's belief about an arriving request is the prior updated by
its tenant's row (``tenant_belief``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .processes import PopulationPriors, PseudoObservations

#: arguments of ``log_gamma_ratio`` below this are shifted up by as many
#: whole steps before the Stirling series is used there
_SHIFT = 4


def log_gamma_ratio(a, p) -> jax.Array:
    """log Gamma(a+p)/Gamma(a) for a > 0 and p >= 0, in the dtype of ``a``.

    Below ``_SHIFT`` the argument is shifted: with x = a + _SHIFT,
    log Gamma(a+p)/Gamma(a) = log Gamma(x+p)/Gamma(x)
    - sum_j log1p(p/(a+j)), j < _SHIFT. At x >= _SHIFT the ratio is

        (x - 1/2) log1p(p/x) + p log(x+p) - p + S(x+p) - S(x),

    S(y) = 1/(12y) - 1/(360y^3) + 1/(1260y^5) - 1/(1680y^7), the Stirling
    series of log Gamma less its leading terms (the next term is below
    4e-9 at x >= 4). No term is a difference of two large numbers, so the
    result keeps a few float32 ulps of its own size at any shape up to 1e7
    and beyond.
    """
    a = jnp.asarray(a)
    p = jnp.asarray(p, a.dtype)
    small = a < _SHIFT
    x = jnp.where(small, a + _SHIFT, a)
    shift = sum(jnp.log1p(p / (a + j)) for j in range(_SHIFT))

    def stirling(y):
        r = 1.0 / y
        r2 = r * r
        return r * (1.0 / 12.0 - r2 * (1.0 / 360.0 - r2 * (
            1.0 / 1260.0 - r2 / 1680.0)))

    big = ((x - 0.5) * jnp.log1p(p / x) + p * jnp.log(x + p) - p
           + (stirling(x + p) - stirling(x)))
    return big - jnp.where(small, shift, 0.0)


def expected_mu_pow(mu_a, mu_b, p) -> jax.Array:
    """E[mu**p] = Gamma(a+p)/Gamma(a) / b**p under mu ~ Gamma(a, b)."""
    return jnp.exp(log_gamma_ratio(mu_a, p) - p * jnp.log(mu_b))


class GammaBelief(NamedTuple):
    """Per-slot Gamma(shape, rate) posteriors for (mu, lam, sig)."""

    mu_a: jax.Array
    mu_b: jax.Array
    lam_a: jax.Array
    lam_b: jax.Array
    sig_a: jax.Array
    sig_b: jax.Array

    def expected_mu_pow(self, p) -> jax.Array:
        """E[mu**p] = Gamma(a+p)/Gamma(a) / b**p under mu ~ Gamma(a, b)."""
        return expected_mu_pow(self.mu_a, self.mu_b, p)


def belief_from_prior(priors: PopulationPriors, shape=()) -> GammaBelief:
    """Fresh belief equal to the population prior for every slot."""
    full = lambda v: jnp.full(shape, v, dtype=jnp.float32)
    return GammaBelief(
        mu_a=full(priors.mu_shape), mu_b=full(priors.mu_rate),
        lam_a=full(priors.lam_shape), lam_b=full(priors.lam_rate),
        sig_a=full(priors.sig_shape), sig_b=full(priors.sig_rate),
    )


def update_on_events(
    bel: GammaBelief,
    *,
    core_deaths: jax.Array,
    exposure_core_hours: jax.Array,
    n_scaleouts: jax.Array,
    scaleout_cores: jax.Array,
    alive_hours: jax.Array,
    priors: PopulationPriors,
) -> GammaBelief:
    """One observation step. All args are per-slot arrays (zeros for no-ops).

    ``exposure_core_hours`` is the total core-hours lived this step (both the
    cores that died and the survivors — right-censored observations add
    exposure to the rate parameter but no count to the shape).
    ``scaleout_cores`` is the total cores requested, so sizes-minus-one sum to
    ``scaleout_cores - n_scaleouts``.
    """
    mu_a = bel.mu_a + core_deaths
    mu_b = bel.mu_b + exposure_core_hours
    # E-step exposure for lam uses the *updated* mu posterior.
    e_mu_nu = expected_mu_pow(mu_a, mu_b, priors.nu)
    lam_a = bel.lam_a + n_scaleouts
    lam_b = bel.lam_b + e_mu_nu * alive_hours
    sig_a = bel.sig_a + (scaleout_cores - n_scaleouts)
    sig_b = bel.sig_b + n_scaleouts
    return GammaBelief(mu_a, mu_b, lam_a, lam_b, sig_a, sig_b)


def apply_pseudo_observations(bel: GammaBelief, obs: PseudoObservations,
                              priors: PopulationPriors) -> GammaBelief:
    """Fold paper-§6 pseudo observations into the belief (deployment-specific prior)."""
    mu_a = bel.mu_a + obs.n_lifetimes
    mu_b = bel.mu_b + obs.sum_lifetimes
    e_mu_nu = expected_mu_pow(mu_a, mu_b, priors.nu)
    lam_a = bel.lam_a + obs.n_scaleouts
    lam_b = bel.lam_b + e_mu_nu * obs.n_windows
    sig_a = bel.sig_a + obs.sum_size_minus1
    sig_b = bel.sig_b + obs.n_sizes
    return GammaBelief(mu_a, mu_b, lam_a, lam_b, sig_a, sig_b)


def observe_initial_size(bel: GammaBelief, c0: jax.Array) -> GammaBelief:
    """The arrival request C0 ~ 1 + Poisson(sig) is itself a size observation."""
    return bel._replace(sig_a=bel.sig_a + (c0 - 1), sig_b=bel.sig_b + 1.0)


def pseudo_counts_from_observables(
    *,
    core_deaths: jax.Array,
    exposure_core_hours: jax.Array,
    n_scaleouts: jax.Array,
    scaleout_cores: jax.Array,
    window_hours: jax.Array,
) -> PseudoObservations:
    """Provider-side pseudo-counts from a deployment's *observed* history.

    The paper's §6 pseudo observations are k draws from each true scaling
    process; a recorded trace carries the real thing — the death/scale-out
    counts and exposures a provider would have logged while the deployment
    ran. Packing those observables into a ``PseudoObservations`` and folding
    them through ``apply_pseudo_observations`` yields exactly the conjugate
    posterior the provider would hold after watching that history:

      * each observed core death is one (censored-exponential) lifetime
        observation; the core-hour exposure is the Gamma rate increment,
        so survivors inform mu through exposure alone;
      * the observation window plays the role of the §6 unit-time windows
        (``n_windows`` is *hours* here, not a count — the conjugate update
        only ever uses it as exposure);
      * each scale-out contributes one size observation with
        size - 1 summing to ``scaleout_cores - n_scaleouts``.

    Inputs may be malformed real-trace columns; counts are clipped at zero
    so a bad row degrades to "no information" rather than an improper
    posterior.
    """
    deaths = jnp.maximum(core_deaths, 0.0)
    n_so = jnp.maximum(n_scaleouts, 0.0)
    return PseudoObservations(
        n_lifetimes=deaths,
        sum_lifetimes=jnp.maximum(exposure_core_hours, 0.0),
        n_windows=jnp.maximum(window_hours, 0.0),
        n_scaleouts=n_so,
        n_sizes=n_so,
        sum_size_minus1=jnp.maximum(
            jnp.maximum(scaleout_cores, 0.0) - n_so, 0.0),
    )


#: a tenant table row: the observations pooled over every deployment of the
#: tenant admitted so far (each admitted request's ``c0`` is a size
#: observation, as ``observe_initial_size`` counts it)
TENANT_COLUMNS = ("core_deaths", "exposure_core_hours", "alive_hours",
                  "n_scaleouts", "size_minus1", "n_sizes")


def tenant_rows(tenant, n_tenants: int, keep=True) -> jax.Array:
    """The table row of each tenant id, as an index. An id outside
    ``[0, n_tenants)`` (a request that names no tenant), or a lane not
    ``keep``, maps one past the table: a gather there reads zeros and a
    scatter there is dropped."""
    t = jnp.asarray(tenant, jnp.int32)
    return jnp.where((t >= 0) & (t < n_tenants) & keep, t, n_tenants)


def init_tenant_table(n_tenants: int) -> jax.Array:
    """An empty tenant table: ``[2, n_tenants, len(TENANT_COLUMNS)]``."""
    return jnp.zeros((2, n_tenants, len(TENANT_COLUMNS)), jnp.float32)


def add_to_tenant_table(table: jax.Array, rows: jax.Array,
                        inc: jax.Array) -> jax.Array:
    """Segment-sum ``inc`` (``[N, C]``) by ``rows`` and add the sums into
    the compensated table. One step's sums are small, so a float32 scatter
    keeps them exact; each goes into ``table[0]`` by an error-free
    two-sum, and the rounding it leaves is carried in ``table[1]``, which
    is renormalized to below half an ulp of the sum. Whole-number columns
    stay exact up to 2**47, where a plain float32 sum stops growing by a
    window's increment once it passes about 2**27."""
    hi, lo = table[0], table[1]
    x = jnp.zeros_like(hi).at[rows].add(inc, mode="drop")
    s = hi + x
    b = s - hi
    e = (hi - (s - b)) + (x - b) + lo
    new_hi = s + e
    return jnp.stack([new_hi, e - (new_hi - s)])


def fold_tenant_events(table: jax.Array, tenant: jax.Array, *,
                       core_deaths, exposure_core_hours, n_scaleouts,
                       scaleout_cores, alive_hours) -> jax.Array:
    """Segment-sum one window's per-slot observations, the arguments
    ``update_on_events`` takes, into each slot's tenant row. A slot that
    holds no deployment observes zeros."""
    inc = jnp.stack([core_deaths, exposure_core_hours, alive_hours,
                     n_scaleouts, scaleout_cores - n_scaleouts, n_scaleouts],
                    axis=-1)
    return add_to_tenant_table(table, tenant_rows(tenant, table.shape[1]),
                               inc)


def fold_tenant_arrivals(table: jax.Array, tenant: jax.Array, c0: jax.Array,
                         placed: jax.Array) -> jax.Array:
    """Each placed arrival's request into its tenant row: ``c0 - 1`` cores
    beyond one and one size observation. Other lanes add nothing."""
    one = jnp.ones_like(c0)
    zero = jnp.zeros_like(c0)
    inc = jnp.stack([zero, zero, zero, zero, c0 - 1.0, one], axis=-1)
    rows = tenant_rows(tenant, table.shape[1], keep=placed)
    return add_to_tenant_table(table, rows, inc)


def tenant_observations(rows: jax.Array) -> PseudoObservations:
    """A tenant row as paper-§6 pseudo observations. Unlike
    ``pseudo_counts_from_observables``, the size observations count the
    admitted requests' ``c0`` besides the scale-outs, so they are a column
    of their own; ``n_windows`` is alive deployment-hours, exposure only."""
    return PseudoObservations(
        n_lifetimes=rows[..., 0], sum_lifetimes=rows[..., 1],
        n_windows=rows[..., 2], n_scaleouts=rows[..., 3],
        n_sizes=rows[..., 5], sum_size_minus1=rows[..., 4])


def tenant_belief(table: jax.Array, tenant: jax.Array, c0: jax.Array,
                  priors: PopulationPriors):
    """``(belief, informed)`` of arriving requests: the prior updated by
    their tenants' rows (each column's compensated sum rounded to float32),
    one E-step for lam over the pooled hours, and then each request's own
    ``c0``; ``informed`` where the row holds at least one admitted
    deployment (a cold row leaves the belief at the prior)."""
    rows = table[0].at[tenant_rows(tenant, table.shape[1])].get(
        mode="fill", fill_value=0.0)
    prior = belief_from_prior(priors, c0.shape)
    bel = apply_pseudo_observations(prior, tenant_observations(rows), priors)
    return observe_initial_size(bel, c0), rows[..., 5] > 0.0
