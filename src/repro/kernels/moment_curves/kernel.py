"""Pallas TPU kernel: fused E[L_t]/V[L_t] moment curves (the paper's policy-
evaluation hot loop, executed for every active deployment on every arrival).

Layout (VPU workload — transcendental-heavy, no MXU except the two small
matmuls that replace cumsum/interp):

  grid  = (ceil(D / BLOCK_D),)           one program per deployment block
  VMEM  in : packed params [BLOCK_D, 16]  (posterior moments + precomputed
             Gamma-continuation factors — gammaln has no Pallas lowering, so
             ops.py computes the per-deployment R(p) factors outside)
         t [1, N] horizon grid, tc/tau [1, ND] D-term checkpoints/lags,
         tril [ND, ND] lower-triangular ones (cumsum-as-matmul),
         w_interp [ND+1, N] linear-interp hat weights (interp-as-matmul)
  VMEM out: EL, VL [BLOCK_D, N]

All math in f32. cumsum and cumprod (via exp∘cumsum∘log) are expressed as
matmuls against the static tril matrix so the kernel lowers on TPU without
relying on scan primitives. Mosaic has no ``expm1`` lowering either, so
``_expm1`` rebuilds it from ``exp`` and a short Taylor polynomial.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.moments import F32

BLOCK_D = 256

# packed parameter columns (ALIVE is consumed only by the aggregate variant)
(A, B, C0, EU, EU2, EL_, ES1, ESS2, RH1, Z1, RK, Z2, EMUNU, DELTA, ALIVE,
 _PAD) = range(16)
N_COLS = 16

#: below this |x| the degree-7 Taylor series of expm1 is used: its
#: truncation error there is under 2e-9 relative, while exp(x) - 1 above it
#: loses at most ~3e-7 relative to cancellation
_EXPM1_TAYLOR_MAX = 0.25


def _expm1(x):
    """``exp(x) - 1`` without the cancellation near 0 (``h1``/``kk`` at
    short horizons sit there); lowers on Mosaic, which has no expm1."""
    taylor = x * (1.0 + x * (1.0 / 2 + x * (1.0 / 6 + x * (
        1.0 / 24 + x * (1.0 / 120 + x * (1.0 / 720 + x * (1.0 / 5040)))))))
    return jnp.where(jnp.abs(x) < _EXPM1_TAYLOR_MAX, taylor, jnp.exp(x) - 1.0)


def _curve_block(p, t_ref, tc_ref, tau_ref, tril_ref, w_ref):
    """Shared kernel body: EL/VL [D, N] for one block of packed params."""
    col = lambda i: p[:, i][:, None]                 # [D, 1]
    a, b, c = col(A), col(B), col(C0)
    eu, eu2, el, es1, ess2 = col(EU), col(EU2), col(EL_), col(ES1), col(ESS2)
    rh1, z1, rk, z2 = col(RH1), col(Z1), col(RK), col(Z2)
    e_mu_nu, delta = col(EMUNU), col(DELTA)

    t = t_ref[...]                                   # [1, N]
    l1 = jnp.log1p(t / b)                            # [D, N]
    l2 = jnp.log1p(2.0 * t / b)

    h1 = rh1 * -_expm1(-z1 * l1)
    h2 = rh1 * -_expm1(-z1 * l2)
    eq = eu * h1
    evq = el * (es1 * h1 + 0.5 * ess2 * h2)
    kk = rk * (-2.0 * _expm1(-z2 * l1) + _expm1(-z2 * l2))
    veq = jnp.maximum(eu2 * kk - eq * eq, 0.0)
    vq = evq + veq

    p1 = jnp.exp(-a * l1)
    p2 = jnp.exp(-a * l2)
    eb = c * p1
    vb = c * (p1 - p2) + c * c * jnp.maximum(p2 - p1 * p1, 0.0)
    em = jnp.exp(-a * jnp.log1p(delta * t / b))
    vm = em * (1.0 - em)

    # --- D-term on uniform checkpoints (lag-cumsum as matmul) -------------
    tc = tc_ref[...]                                 # [1, ND]
    tau = tau_ref[...]                               # [1, ND]
    w_step = tc[0, 0]                                # checkpoint spacing
    q = eu * e_mu_nu                                 # [D, 1]
    p_lag = jnp.exp(-a * jnp.log1p(tau / b))
    s = (q * w_step) * jnp.log1p(-jnp.minimum(p_lag, 1.0 - 1e-7))
    cums = jax.lax.dot_general(
        s, tril_ref[...], (((1,), (0,)), ((), ())),     # inclusive cumsum
        precision=F32, preferred_element_type=jnp.float32)
    p_self = jnp.exp(-a * jnp.log1p(tc / b))
    log_dead = c * jnp.log1p(-jnp.minimum(p_self, 1.0 - 1e-7)) + cums
    factor = jnp.maximum(-_expm1(log_dead), 1e-37)
    logf = jnp.log(factor)
    log_ed = jax.lax.dot_general(
        logf, tril_ref[...], (((1,), (0,)), ((), ())),
        precision=F32, preferred_element_type=jnp.float32)
    ed_sub = jnp.exp(log_ed)                         # cumprod [D, ND]
    ones = jnp.ones_like(ed_sub[:, :1])
    ed_ext = jnp.concatenate([ones, ed_sub], axis=1)  # anchor (t=0, 1)
    ed = jax.lax.dot_general(
        ed_ext, w_ref[...], (((1,), (0,)), ((), ())),   # [D, N]
        precision=F32, preferred_element_type=jnp.float32)
    vd = ed * (1.0 - ed)

    er = eq + eb
    vr = vq + vb
    edr = ed * er
    vdr = vd * vr + vd * er * er + ed * ed * vr
    return em * edr, vm * vdr + vm * edr * edr + em * em * vdr


def _kernel(params_ref, t_ref, tc_ref, tau_ref, tril_ref, w_ref,
            el_ref, vl_ref):
    p = params_ref[...].astype(jnp.float32)          # [D, 16]
    el, vl = _curve_block(p, t_ref, tc_ref, tau_ref, tril_ref, w_ref)
    el_ref[...] = el
    vl_ref[...] = vl


def _agg_kernel(params_ref, t_ref, tc_ref, tau_ref, tril_ref, w_ref,
                el_ref, vl_ref):
    """Aggregated-output variant: the [BLOCK_D, N] curve block never leaves
    VMEM — each program masks dead slots (ALIVE column) and accumulates its
    partial sums into the shared [1, N] outputs across sequential grid
    steps."""

    @pl.when(pl.program_id(0) == 0)
    def _init():
        el_ref[...] = jnp.zeros_like(el_ref)
        vl_ref[...] = jnp.zeros_like(vl_ref)

    p = params_ref[...].astype(jnp.float32)          # [D, 16]
    el, vl = _curve_block(p, t_ref, tc_ref, tau_ref, tril_ref, w_ref)
    mask = p[:, ALIVE][:, None]
    el_ref[...] += jnp.sum(el * mask, axis=0, keepdims=True)
    vl_ref[...] += jnp.sum(vl * mask, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("nd", "interpret"))
def moment_curves_packed(params: jax.Array, t_grid: jax.Array,
                         tc: jax.Array, tau: jax.Array, w_interp: jax.Array,
                         *, nd: int, interpret: bool = False):
    """params: [D, 16] (padded to BLOCK_D multiple); t_grid: [1, N];
    tc/tau: [1, ND]; w_interp: [ND+1, N]. Returns (EL, VL) [D, N]."""
    d, _ = params.shape
    n = t_grid.shape[1]
    assert d % BLOCK_D == 0, d
    tril = jnp.tril(jnp.ones((nd, nd), jnp.float32)).T  # [lag, ckpt]
    grid = (d // BLOCK_D,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_D, N_COLS), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, nd), lambda i: (0, 0)),
            pl.BlockSpec((1, nd), lambda i: (0, 0)),
            pl.BlockSpec((nd, nd), lambda i: (0, 0)),
            pl.BlockSpec((nd + 1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_D, n), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_D, n), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, n), jnp.float32),
            jax.ShapeDtypeStruct((d, n), jnp.float32),
        ],
        interpret=interpret,
    )(params, t_grid, tc, tau, tril, w_interp)


@functools.partial(jax.jit, static_argnames=("nd", "interpret"))
def moment_curves_agg_packed(params: jax.Array, t_grid: jax.Array,
                             tc: jax.Array, tau: jax.Array,
                             w_interp: jax.Array, *, nd: int,
                             interpret: bool = False):
    """Aggregate (sum over rows with ALIVE=1) moment curves.

    Same inputs as ``moment_curves_packed`` with the ALIVE column populated;
    returns (EL, VL) each [1, N] — the masked sums over all D rows.
    """
    d, _ = params.shape
    n = t_grid.shape[1]
    assert d % BLOCK_D == 0, d
    tril = jnp.tril(jnp.ones((nd, nd), jnp.float32)).T  # [lag, ckpt]
    grid = (d // BLOCK_D,)
    return pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_D, N_COLS), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, nd), lambda i: (0, 0)),
            pl.BlockSpec((1, nd), lambda i: (0, 0)),
            pl.BlockSpec((nd, nd), lambda i: (0, 0)),
            pl.BlockSpec((nd + 1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        # every grid step accumulates into the same [1, N] output block, so
        # the steps must run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(params, t_grid, tc, tau, tril, w_interp)
