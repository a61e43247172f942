"""Closed-form moment curves E[L_t], V[L_t] of a deployment's future size.

This is the computational heart of the paper (Props. 2, 3, 5): under the
provider's Gamma belief (a,b)=(mu_a,mu_b), (al,bl)=(lam_a,lam_b),
(as,bs)=(sig_a,sig_b) for a deployment with C active cores, the future size is

    L_t = M_t * D_t * (Q_t + B_t)

with (paper §4) B_t = surviving initial cores, Q_t = surviving scale-out cores,
M_t = max-lifetime survival, D_t = "has not died from zero cores". Factors are
treated as uncorrelated (the paper's stated approximation).

Two evaluation paths are provided:

* ``moment_curves`` — **continuous-time closed forms** (re-derived; DESIGN.md
  §4). Every horizon point costs O(1) (no inner sum over past steps), so a full
  curve over an *arbitrary* (e.g. geometric) grid is O(N). This is the
  optimized, beyond-paper formulation and the oracle for the Pallas kernel.

* ``moment_curves_discrete`` — the **paper-faithful** uniform-grid formulation
  (Poisson counts per step, Prop. 5 sums), evaluated for all n=1..N at once in
  O(N) total via prefix sums (the paper evaluates each n in O(n), i.e. O(N²)
  per curve). ``moment_curves_discrete_naive`` is the direct O(N²)/O(N³)
  transcription used as a test oracle for the prefix-sum indexing.

Key Gamma integrals (mu ~ Gamma(a, b), rate parameterization):

    g(p, t) = E[mu^p e^(-t mu)]        = R(p) b^-p (1 + t/b)^-(a+p)
    H(p, t) = E[mu^p (1 - e^(-t mu))]  = R(p) b^-p (1 - (1+t/b)^-(a+p))
    K(p, t) = E[mu^p (1 - e^(-t mu))²] = R(p) b^-p (1 - 2(1+t/b)^-(a+p)
                                                      + (1+2t/b)^-(a+p))
    R(p)    = Gamma(a+p)/Gamma(a)

H and K stay valid by analytic continuation for a+p < 0 (the case for the
fitted Azure priors, where a + nu - 1 = -0.0163): we evaluate them through
``Gamma(a+p+1)/Gamma(a) / (a+p)`` and ``expm1`` so the removable
singularity at a+p = 0 never produces a NaN. Every ratio R is formed by
``core.belief.log_gamma_ratio``, accurate at the large shapes a heavily
observed belief reaches.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .belief import GammaBelief, log_gamma_ratio
from .processes import PopulationPriors

_EPS = 1e-12

#: contraction precision of every matmul on the moment-curve path: a TPU's
#: default rounds f32 operands to bf16 (about 3 significant digits); this
#: keeps them f32 there and changes nothing on CPU
F32 = jax.lax.Precision.HIGHEST


class MomentCurves(NamedTuple):
    """E and V of L over the horizon grid; shapes [..., N]."""

    EL: jax.Array
    VL: jax.Array


# ---------------------------------------------------------------------------
# Gamma-integral helpers. All take a, b with trailing broadcast vs t.
# ---------------------------------------------------------------------------

def _g(a, b, p, t):
    """E[mu^p e^(-t mu)]; requires a + p > 0 (true for p in {0, nu, 2nu})."""
    logr = log_gamma_ratio(a, p)
    return jnp.exp(logr - p * jnp.log(b) - (a + p) * jnp.log1p(t / b))


def _h(a, b, p, t):
    """E[mu^p (1 - e^(-t mu))], valid for a + p > -1 (analytic continuation)."""
    z = a + p
    z = jnp.where(jnp.abs(z) < _EPS, _EPS, z)
    logr1 = log_gamma_ratio(a, p + 1.0)  # log Gamma(a+p+1)/Gamma(a)
    bracket = -jnp.expm1(-z * jnp.log1p(t / b))
    return jnp.exp(logr1 - p * jnp.log(b)) * bracket / z


def _k(a, b, p, t):
    """E[mu^p (1 - e^(-t mu))²], valid for a + p > -2 if a + 2p' terms converge."""
    z = a + p
    z = jnp.where(jnp.abs(z) < _EPS, _EPS, z)
    logr1 = log_gamma_ratio(a, p + 1.0)
    l1 = jnp.log1p(t / b)
    l2 = jnp.log1p(2.0 * t / b)
    bracket = -2.0 * jnp.expm1(-z * l1) + jnp.expm1(-z * l2)
    return jnp.exp(logr1 - p * jnp.log(b)) * bracket / z


def _sigma_moments(bel: GammaBelief):
    """E[sigma+1], E[(sigma+1)^2], E[sigma(sigma+2)] under Gamma(as, bs)."""
    es = bel.sig_a / bel.sig_b
    es2 = bel.sig_a * (bel.sig_a + 1.0) / bel.sig_b**2
    e_s1 = es + 1.0
    e_s1_sq = es2 + 2.0 * es + 1.0
    e_ss2 = es2 + 2.0 * es
    return e_s1, e_s1_sq, e_ss2


def _lam_moments(bel: GammaBelief):
    el = bel.lam_a / bel.lam_b
    el2 = bel.lam_a * (bel.lam_a + 1.0) / bel.lam_b**2
    return el, el2


def _product_var(ex, vx, ey, vy):
    """V[XY] for independent X, Y."""
    return vx * vy + vx * ey**2 + ex**2 * vy


# ---------------------------------------------------------------------------
# D-term: probability the deployment has not hit zero cores (paper Prop. 2).
#
# The paper's recursion (16)-(17) multiplies, per step j, the probability that
# not every core is dead:  1 - (1-P(t_j))^C * prod_{i<j} (1-P(t_j-t_i))^{q_i}
# with P(t) = E[e^(-t mu)] (Lomax survival) and q_i the expected cores added
# in window i. On a *uniform* checkpoint grid the elapsed time t_j - t_i
# depends only on the lag j-i, so the inner product is a single cumulative sum
# over lags — O(Nd) for the whole curve instead of the paper's O(Nd²).
# ---------------------------------------------------------------------------

def _d_curve_uniform(a, b, eu, e_mu_nu, cores, w, nd: int, *, midpoint: bool):
    """E[D] at uniform checkpoints t_j = w*j, j=1..nd. Leading dims broadcast.

    midpoint=False reproduces the paper exactly (windows i < j, elapsed
    (j-i)*w). midpoint=True also counts the current window at half-window
    elapsed time — the midpoint-rule variant used by the continuous path so a
    coarse checkpoint grid does not spuriously kill young deployments.
    """
    q = eu * e_mu_nu  # expected cores added per hour
    lags = jnp.arange(nd, dtype=w.dtype if hasattr(w, "dtype") else jnp.float32)
    if midpoint:
        tau = w * (lags + 0.5)              # l = 0..nd-1
    else:
        tau = w * (lags + 1.0)              # l = 1..nd-1 used (see shift below)
    p_lag = jnp.exp(-a[..., None] * jnp.log1p(tau / b[..., None]))
    s = (q * w)[..., None] * jnp.log1p(-jnp.clip(p_lag, None, 1.0 - 1e-7))
    cums = jnp.cumsum(s, axis=-1)
    if midpoint:
        # sum over lags 0..j-1 -> cums[j-1]
        window_sum = cums
    else:
        # sum over lags 1..j-1 -> shift right by one (0 for j=1)
        window_sum = jnp.concatenate(
            [jnp.zeros_like(cums[..., :1]), cums[..., :-1]], axis=-1
        )
    tc = w * jnp.arange(1, nd + 1)
    p_self = jnp.exp(-a[..., None] * jnp.log1p(tc / b[..., None]))
    log_dead = (
        cores[..., None] * jnp.log1p(-jnp.clip(p_self, None, 1.0 - 1e-7))
        + window_sum
    )
    factor = -jnp.expm1(log_dead)  # 1 - Pr(all cores dead at t_j)
    return jnp.cumprod(factor, axis=-1)


def _interp_rows(t_full, ts, ys):
    """Piecewise-linear interp of per-slot curves ys [..., Nd] from grid ts [Nd]
    (with implicit (0, 1) left anchor) onto t_full [N]."""
    ts0 = jnp.concatenate([jnp.zeros((1,), ts.dtype), ts])
    ones = jnp.ones(ys.shape[:-1] + (1,), ys.dtype)
    ys0 = jnp.concatenate([ones, ys], axis=-1)
    flat = ys0.reshape((-1, ys0.shape[-1]))
    out = jax.vmap(lambda row: jnp.interp(t_full, ts0, row))(flat)
    return out.reshape(ys.shape[:-1] + (t_full.shape[-1],))


# ---------------------------------------------------------------------------
# Continuous-time closed forms (optimized path; DESIGN.md §4).
# ---------------------------------------------------------------------------

def moment_curves(
    bel: GammaBelief,
    cores: jax.Array,
    t_grid: jax.Array,
    priors: PopulationPriors,
    *,
    d_points: int = 32,
    d_stride: int | None = None,  # legacy alias: d_points = N // d_stride
) -> MomentCurves:
    """E[L_t], V[L_t] at horizon times ``t_grid`` [N] (hours from now).

    ``bel`` fields and ``cores`` share a batch shape [...]; output [..., N].
    ``d_points``: the D-term (zero-core death) runs on a uniform checkpoint
    grid of this many points spanning (0, max(t_grid)] and is linearly
    interpolated onto ``t_grid``.
    """
    nu = priors.nu
    a, b = bel.mu_a[..., None], bel.mu_b[..., None]
    el, el2 = _lam_moments(bel)
    e_s1, e_s1_sq, e_ss2 = _sigma_moments(bel)
    eu = el * e_s1
    eu2 = el2 * e_s1_sq
    t = t_grid
    c = cores[..., None].astype(t_grid.dtype)

    # --- Q: scale-out cores still alive -----------------------------------
    h1 = _h(a, b, nu - 1.0, t)
    eq = eu[..., None] * h1
    evq = el[..., None] * (e_s1[..., None] * h1 + 0.5 * e_ss2[..., None] * _h(a, b, nu - 1.0, 2.0 * t))
    veq = eu2[..., None] * _k(a, b, 2.0 * nu - 2.0, t) - eq**2
    vq = evq + jnp.maximum(veq, 0.0)

    # --- B: initial cores still alive --------------------------------------
    p1 = _g(a, b, 0.0, t)
    p2 = _g(a, b, 0.0, 2.0 * t)
    ebn = c * p1
    vb = c * (p1 - p2) + c**2 * jnp.maximum(p2 - p1**2, 0.0)

    # --- M: max-lifetime survival ------------------------------------------
    em = jnp.exp(-a * jnp.log1p(priors.delta * t / b))
    vm = em * (1.0 - em)

    # --- D: zero-core death ------------------------------------------------
    if d_stride is not None:
        d_points = max(4, t_grid.shape[-1] // d_stride)
    e_mu_nu = bel.expected_mu_pow(nu)
    w = t_grid[-1] / d_points
    ed_sub = _d_curve_uniform(bel.mu_a, bel.mu_b, eu, e_mu_nu,
                              cores.astype(t_grid.dtype), w, d_points,
                              midpoint=True)
    tc = w * jnp.arange(1, d_points + 1)
    ed = _interp_rows(t_grid, tc, ed_sub)
    vd = ed * (1.0 - ed)

    # --- compose L = M * D * (Q + B) ---------------------------------------
    er = eq + ebn
    vr = vq + vb
    edr = ed * er
    vdr = _product_var(ed, vd, er, vr)
    elc = em * edr
    vl = _product_var(em, vm, edr, vdr)
    return MomentCurves(EL=elc, VL=vl)


# ---------------------------------------------------------------------------
# Paper-faithful discrete formulation (Prop. 5 sums via prefix sums).
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# Fused-aggregate fast path (beyond-paper; the simulator's per-step hot loop).
#
# The admission policies only consume the cluster-wide sums over alive slots,
# sum_s E[L^s_t] and sum_s V[L^s_t] — the per-slot [S, N] curves are an
# intermediate. ``aggregate_moment_curves`` computes the masked sums directly:
# per-slot Gamma-continuation factors are packed once (the Gamma-ratio part,
# shared with the Pallas kernel's packing in kernels/moment_curves/ops.py),
# curve blocks of ``block_size`` slots are evaluated with shared log1p
# subexpressions and matmul interpolation, and each block is reduced into the
# [N] accumulator inside a lax.scan — peak memory is [block_size, N], never
# [S, N]. The same packed math is exposed per-slot as ``moment_curves_fused``
# so the aggregate can be equivalence-tested against the per-slot reference.
# ---------------------------------------------------------------------------

class PackedBelief(NamedTuple):
    """Per-slot scalar factors of the moment-curve closed forms.

    Everything that needs a Gamma ratio (the costliest per-slot scalar
    work, kept out of the Pallas kernel) is precomputed here; curve
    evaluation from a PackedBelief touches only log1p/expm1/exp.
    """

    a: jax.Array        # mu posterior shape
    b: jax.Array        # mu posterior rate
    cores: jax.Array    # current active cores C
    eu: jax.Array       # E[lam] E[sig+1]
    eu2: jax.Array      # E[lam^2] E[(sig+1)^2]
    el: jax.Array       # E[lam]
    es1: jax.Array      # E[sig+1]
    ess2: jax.Array     # E[sig(sig+2)]
    rh1: jax.Array      # H-integral continuation factor at p = nu-1
    z1: jax.Array       # a + nu - 1 (clamped away from 0)
    rk: jax.Array       # K-integral continuation factor at p = 2nu-2
    z2: jax.Array       # a + 2nu - 2 (clamped away from 0)
    e_mu_nu: jax.Array  # E[mu^nu]


def pack_belief(bel: GammaBelief, cores: jax.Array,
                priors: PopulationPriors) -> PackedBelief:
    """Precompute the per-slot factors; shapes follow ``bel`` fields."""
    nu = priors.nu
    a, b = bel.mu_a, bel.mu_b
    el, el2 = _lam_moments(bel)
    e_s1, e_s1_sq, e_ss2 = _sigma_moments(bel)

    z1 = a + nu - 1.0
    z1 = jnp.where(jnp.abs(z1) < _EPS, _EPS, z1)
    log_b = jnp.log(b)
    r_nu = log_gamma_ratio(a, nu)
    rh1 = jnp.exp(r_nu - (nu - 1.0) * log_b) / z1
    z2 = a + 2.0 * nu - 2.0
    z2 = jnp.where(jnp.abs(z2) < _EPS, _EPS, z2)
    rk = jnp.exp(log_gamma_ratio(a, 2.0 * nu - 1.0)
                 - (2.0 * nu - 2.0) * log_b) / z2
    e_mu_nu = jnp.exp(r_nu - nu * log_b)
    return PackedBelief(
        a=a, b=b, cores=cores.astype(a.dtype), eu=el * e_s1,
        eu2=el2 * e_s1_sq, el=el, es1=e_s1, ess2=e_ss2, rh1=rh1, z1=z1,
        rk=rk, z2=z2, e_mu_nu=e_mu_nu,
    )


def interp_matrix(t_grid: jax.Array, nd: int):
    """D-term checkpoint grids + linear-interp weights as one matmul.

    Returns (tc [ND] checkpoint times, tau [ND] midpoint lags,
    w_mat [ND+1, N] hat-function weights with the implicit (0, 1) anchor in
    row 0) such that ``ed_ext @ w_mat == interp(t_grid)`` for piecewise-linear
    interpolation from the uniform checkpoint grid.
    """
    t_max = t_grid[-1]
    w = t_max / nd
    x = jnp.arange(nd + 1, dtype=jnp.float32) * w
    idx = jnp.clip(jnp.searchsorted(x, t_grid, side="right") - 1, 0, nd - 1)
    frac = (t_grid - x[idx]) / w
    w_mat = (
        jax.nn.one_hot(idx, nd + 1, axis=0) * (1.0 - frac)[None, :]
        + jax.nn.one_hot(idx + 1, nd + 1, axis=0) * frac[None, :]
    )
    tc = x[1:]
    tau = w * (jnp.arange(nd, dtype=jnp.float32) + 0.5)
    return tc, tau, w_mat.astype(jnp.float32)


def _curves_from_packed(p: PackedBelief, t_grid: jax.Array,
                        w_mat: jax.Array, priors: PopulationPriors,
                        nd: int) -> MomentCurves:
    """Curves [..., N] from packed factors; log1p(t/b) / log1p(2t/b) shared
    across the Q/B/M factors, D-term interpolated via one matmul."""
    t = t_grid
    a, b, c = p.a[..., None], p.b[..., None], p.cores[..., None]
    l1 = jnp.log1p(t / b)
    l2 = jnp.log1p(2.0 * t / b)

    h1 = p.rh1[..., None] * -jnp.expm1(-p.z1[..., None] * l1)
    h2 = p.rh1[..., None] * -jnp.expm1(-p.z1[..., None] * l2)
    eq = p.eu[..., None] * h1
    evq = p.el[..., None] * (p.es1[..., None] * h1
                             + 0.5 * p.ess2[..., None] * h2)
    kk = p.rk[..., None] * (-2.0 * jnp.expm1(-p.z2[..., None] * l1)
                            + jnp.expm1(-p.z2[..., None] * l2))
    veq = p.eu2[..., None] * kk - eq**2
    vq = evq + jnp.maximum(veq, 0.0)

    p1 = jnp.exp(-a * l1)
    p2 = jnp.exp(-a * l2)
    ebn = c * p1
    vb = c * (p1 - p2) + c**2 * jnp.maximum(p2 - p1**2, 0.0)
    em = jnp.exp(-a * jnp.log1p(priors.delta * t / b))
    vm = em * (1.0 - em)

    w = t_grid[-1] / nd
    ed_sub = _d_curve_uniform(p.a, p.b, p.eu, p.e_mu_nu, p.cores, w, nd,
                              midpoint=True)
    ones = jnp.ones(ed_sub.shape[:-1] + (1,), ed_sub.dtype)
    ed = jnp.matmul(jnp.concatenate([ones, ed_sub], axis=-1), w_mat,
                    precision=F32)
    vd = ed * (1.0 - ed)

    er = eq + ebn
    vr = vq + vb
    edr = ed * er
    vdr = _product_var(ed, vd, er, vr)
    elc = em * edr
    vl = _product_var(em, vm, edr, vdr)
    return MomentCurves(EL=elc, VL=vl)


def moment_curves_fused(
    bel: GammaBelief,
    cores: jax.Array,
    t_grid: jax.Array,
    priors: PopulationPriors,
    *,
    d_points: int = 32,
) -> MomentCurves:
    """Per-slot curves via the packed fast path — same closed forms and
    midpoint D-term as ``moment_curves``; only subexpression sharing and the
    matmul interpolation differ (agreement to ~1e-6 relative)."""
    packed = pack_belief(bel, cores, priors)
    _, _, w_mat = interp_matrix(t_grid.astype(jnp.float32), d_points)
    return _curves_from_packed(packed, t_grid, w_mat, priors, d_points)


def aggregate_moment_curves(
    bel: GammaBelief,
    cores: jax.Array,
    alive: jax.Array,
    t_grid: jax.Array,
    priors: PopulationPriors,
    *,
    d_points: int = 32,
    block_size: int = 512,
) -> MomentCurves:
    """Cluster-wide (sum over alive slots) E[L_t] and V[L_t], shapes [N].

    Dead slots are masked inside the block reduction; the full [S, N] curve
    matrix is never materialized (peak intermediate: [block_size, N]).
    Equivalent to ``moment_curves(...)`` summed over ``alive`` slots.
    """
    s = cores.shape[-1]
    packed = pack_belief(bel, cores, priors)
    mask = alive.astype(t_grid.dtype)
    _, _, w_mat = interp_matrix(t_grid.astype(jnp.float32), d_points)

    if s <= block_size:
        cur = _curves_from_packed(packed, t_grid, w_mat, priors, d_points)
        return MomentCurves(EL=_masked_sum(cur.EL, mask),
                            VL=_masked_sum(cur.VL, mask))

    pad = (-s) % block_size
    if pad:
        # filler slots: benign parameters, masked out of the reduction
        packed = jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.ones(x.shape[:-1] + (pad,), x.dtype)], axis=-1),
            packed)
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-1] + (pad,), mask.dtype)], axis=-1)
    n_blocks = (s + pad) // block_size
    to_blocks = lambda x: jnp.moveaxis(
        x.reshape(x.shape[:-1] + (n_blocks, block_size)), -2, 0)
    blocks = jax.tree.map(to_blocks, packed)
    mask_b = to_blocks(mask)

    n = t_grid.shape[-1]
    zero = jnp.zeros(mask.shape[:-1] + (n,), t_grid.dtype)

    def body(carry, xs):
        el_acc, vl_acc = carry
        pk, mk = xs
        cur = _curves_from_packed(pk, t_grid, w_mat, priors, d_points)
        el_acc = el_acc + _masked_sum(cur.EL, mk)
        vl_acc = vl_acc + _masked_sum(cur.VL, mk)
        return (el_acc, vl_acc), None

    (el, vl), _ = jax.lax.scan(body, (zero, zero), (blocks, mask_b))
    return MomentCurves(EL=el, VL=vl)


def _masked_sum(x: jax.Array, mask: jax.Array) -> jax.Array:
    """``sum_s x[..., s, n] * mask[..., s]`` as one f32 contraction."""
    return jnp.einsum("...sn,...s->...n", x, mask, precision=F32)


def masked_curve_reduction(curves: MomentCurves, mask: jax.Array,
                           block_size: int = 512) -> MomentCurves:
    """Reduce already-evaluated per-slot curves ``[S, N]`` to the masked
    cluster aggregate ``[N]`` with the **exact reduction structure** of
    ``aggregate_moment_curves``: one einsum up to ``block_size`` slots, a
    left-fold of per-``block_size``-block einsums beyond.

    This exists for callers that evaluate the per-slot curves elsewhere —
    the device-sharded admission core evaluates each shard's curves locally,
    all-gathers them, and reduces here — and must still reproduce the fused
    aggregate bit-for-bit: floating-point sums are order-sensitive, so only
    the same block split and the same left-fold over blocks gives the same
    result as the unsharded path. Keep this in lockstep with
    ``aggregate_moment_curves`` (equivalence is pinned in
    ``tests/test_aggregate_fastpath.py``).
    """
    s = mask.shape[-1]
    if s <= block_size:
        return MomentCurves(
            EL=_masked_sum(curves.EL, mask),
            VL=_masked_sum(curves.VL, mask))

    pad = (-s) % block_size
    if pad:
        # filler slots contribute 0 * finite = 0, exactly as the fused
        # path's mask-zeroed benign filler slots do
        curves = jax.tree.map(
            lambda x: jnp.concatenate(
                [x, jnp.zeros(x.shape[:-2] + (pad, x.shape[-1]), x.dtype)],
                axis=-2),
            curves)
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-1] + (pad,), mask.dtype)], axis=-1)
    n_blocks = (s + pad) // block_size
    n = curves.EL.shape[-1]
    to_blocks_c = lambda x: jnp.moveaxis(
        x.reshape(x.shape[:-2] + (n_blocks, block_size, n)), -3, 0)
    blocks = jax.tree.map(to_blocks_c, curves)
    mask_b = jnp.moveaxis(
        mask.reshape(mask.shape[:-1] + (n_blocks, block_size)), -2, 0)
    zero = jnp.zeros(mask.shape[:-1] + (n,), curves.EL.dtype)

    def body(carry, xs):
        el_acc, vl_acc = carry
        cur, mk = xs
        el_acc = el_acc + _masked_sum(cur.EL, mk)
        vl_acc = vl_acc + _masked_sum(cur.VL, mk)
        return (el_acc, vl_acc), None

    (el, vl), _ = jax.lax.scan(body, (zero, zero), (blocks, mask_b))
    return MomentCurves(EL=el, VL=vl)


def moment_curves_discrete(
    bel: GammaBelief,
    cores: jax.Array,
    n_steps: int,
    dt: float,
    priors: PopulationPriors,
    **_legacy,
) -> MomentCurves:
    """Uniform-grid curves at t = dt*(1..n_steps), per the paper's Prop. 5.

    Scale-outs are Poisson *per step* (count ~ Pois(lam mu^nu dt)); a core
    added in step i survives to step n w.p. e^(-(n-i) dt mu). All n evaluated
    simultaneously with prefix sums (O(N) total instead of the paper's O(N²)).
    """
    nu = priors.nu
    a, b = bel.mu_a[..., None], bel.mu_b[..., None]
    el, el2 = _lam_moments(bel)
    e_s1, e_s1_sq, e_ss2 = _sigma_moments(bel)
    eu, eu2 = el * e_s1, el2 * e_s1_sq

    n = n_steps
    d = jnp.arange(n, dtype=jnp.float32)       # elapsed steps n - i = 0..n-1
    s = jnp.arange(2 * n - 1, dtype=jnp.float32)
    g1 = _g(a, b, nu, d * dt)                  # [..., n]
    g2 = _g(a, b, nu, 2.0 * d * dt)
    g3 = _g(a, b, 2.0 * nu, s * dt)            # [..., 2n-1]

    cs1 = jnp.cumsum(g1, axis=-1)              # sum_{d=0}^{m} g1
    cs2 = jnp.cumsum(g2, axis=-1)
    a3 = jnp.cumsum(g3, axis=-1)
    b3 = jnp.cumsum(s * g3, axis=-1)

    nn = jnp.arange(1, n + 1, dtype=jnp.float32)
    i_nm1 = jnp.arange(0, n)                   # index n-1
    i_2nm2 = jnp.arange(0, 2 * n, 2)           # index 2n-2

    ew = jnp.take(cs1, i_nm1, axis=-1)
    eq = eu[..., None] * dt * ew
    evq = el[..., None] * dt * (
        e_s1[..., None] * jnp.take(cs1, i_nm1, axis=-1)
        + e_ss2[..., None] * jnp.take(cs2, i_nm1, axis=-1)
    )
    # E[W_n^2] = sum_{s=0}^{2n-2} min(s+1, 2n-1-s) g3(s)
    a_n = jnp.take(a3, i_nm1, axis=-1)
    b_n = jnp.take(b3, i_nm1, axis=-1)
    a_2n = jnp.take(a3, i_2nm2, axis=-1)
    b_2n = jnp.take(b3, i_2nm2, axis=-1)
    ew2 = (b_n + a_n) + ((2.0 * nn - 1.0) * (a_2n - a_n) - (b_2n - b_n))
    veq = eu2[..., None] * dt**2 * ew2 - (eu[..., None] * dt * ew) ** 2
    vq = evq + jnp.maximum(veq, 0.0)

    t = nn * dt
    c = cores[..., None].astype(jnp.float32)
    p1 = _g(a, b, 0.0, t)
    p2 = _g(a, b, 0.0, 2.0 * t)
    ebn = c * p1
    vb = c * (p1 - p2) + c**2 * jnp.maximum(p2 - p1**2, 0.0)
    em = jnp.exp(-a * jnp.log1p(priors.delta * t / b))
    vm = em * (1.0 - em)

    # Paper-exact D recursion on the uniform step grid (lag-cumsum, O(N)).
    e_mu_nu = bel.expected_mu_pow(nu)
    ed = _d_curve_uniform(bel.mu_a, bel.mu_b, eu, e_mu_nu,
                          cores.astype(jnp.float32), jnp.float32(dt), n,
                          midpoint=False)
    vd = ed * (1.0 - ed)

    er = eq + ebn
    vr = vq + vb
    edr = ed * er
    vdr = _product_var(ed, vd, er, vr)
    elc = em * edr
    vl = _product_var(em, vm, edr, vdr)
    return MomentCurves(EL=elc, VL=vl)


def moment_curves_discrete_naive(
    bel_np, cores, n_steps: int, dt: float, priors: PopulationPriors
) -> MomentCurves:
    """Direct O(N²) numpy transcription of the discrete sums — test oracle.

    ``bel_np``: GammaBelief of scalar floats; ``cores``: scalar.
    """
    from math import lgamma

    a, b = float(bel_np.mu_a), float(bel_np.mu_b)
    al, bl = float(bel_np.lam_a), float(bel_np.lam_b)
    asg, bsg = float(bel_np.sig_a), float(bel_np.sig_b)
    nu, delta = priors.nu, priors.delta

    def g(p, tau):
        return np.exp(lgamma(a + p) - lgamma(a) - p * np.log(b) - (a + p) * np.log1p(tau / b))

    el = al / bl
    el2 = al * (al + 1) / bl**2
    es = asg / bsg
    es2 = asg * (asg + 1) / bsg**2
    e_s1, e_s1_sq, e_ss2 = es + 1, es2 + 2 * es + 1, es2 + 2 * es
    eu, eu2 = el * e_s1, el2 * e_s1_sq
    e_mu_nu = g(nu, 0.0)

    n_arr = np.arange(1, n_steps + 1)
    eq = np.zeros(n_steps); vq = np.zeros(n_steps)
    ebv = np.zeros(n_steps); vb = np.zeros(n_steps)
    em = np.zeros(n_steps); ed = np.zeros(n_steps)
    for ni, n in enumerate(n_arr):
        ew = sum(g(nu, (n - i) * dt) for i in range(1, n + 1))
        eq[ni] = eu * dt * ew
        evq = el * dt * sum(
            e_s1 * g(nu, (n - i) * dt) + e_ss2 * g(nu, 2 * (n - i) * dt)
            for i in range(1, n + 1)
        )
        ew2 = sum(
            g(2 * nu, (2 * n - i - j) * dt)
            for i in range(1, n + 1) for j in range(1, n + 1)
        )
        veq = eu2 * dt**2 * ew2 - (eu * dt * ew) ** 2
        vq[ni] = evq + max(veq, 0.0)
        t = n * dt
        p1, p2 = g(0.0, t), g(0.0, 2 * t)
        ebv[ni] = cores * p1
        vb[ni] = cores * (p1 - p2) + cores**2 * max(p2 - p1**2, 0.0)
        em[ni] = np.exp(-a * np.log1p(delta * t / b))

    # D recursion, paper (16)-(17) on the uniform grid
    ed_prev = 1.0
    q_step = eu * e_mu_nu * dt
    for ni, n in enumerate(n_arr):
        p_self = g(0.0, n * dt)
        log_dead = cores * np.log1p(-min(p_self, 1 - 1e-7))
        for i in range(1, n):
            pij = g(0.0, (n - i) * dt)
            log_dead += q_step * np.log1p(-min(pij, 1 - 1e-7))
        factor = -np.expm1(log_dead)
        ed[ni] = (ed_prev if ni else 1.0) * factor
        ed_prev = ed[ni]

    vm = em * (1 - em)
    vd = ed * (1 - ed)
    er, vr = eq + ebv, vq + vb
    edr = ed * er
    vdr = vd * vr + vd * er**2 + ed**2 * vr
    elc = em * edr
    vl = vm * vdr + vm * edr**2 + em**2 * vdr
    return MomentCurves(EL=elc, VL=vl)
