"""jit wrappers for the moment_curves Pallas kernels.

Packs the GammaBelief into the kernels' [D, 16] parameter layout via
``core.moments.pack_belief`` (the Gamma-function continuation factors are
precomputed outside the kernel — gammaln has no Pallas lowering), builds the
D-term checkpoint grids and the interp-as-matmul weights, pads D to the block
size, and unpacks MomentCurves.

Two entry points:

* ``moment_curves_kernel`` — per-deployment curves [D, N]; drop-in
  replacement for ``core.moments.moment_curves`` (same approximation
  choices: midpoint D-term on ``d_points`` uniform checkpoints).
* ``aggregate_moment_curves_kernel`` — cluster-wide masked sums [N]; the
  fused-aggregate fast path (mask dead slots inside the kernel reduction,
  never materialize [D, N] outside VMEM). Drop-in replacement for
  ``core.moments.aggregate_moment_curves``.

Both compile through Mosaic when JAX's default backend is a TPU and run in
interpret mode everywhere else (``resolve_interpret``) — on CPU a
first-class, tested fallback path, not just a debugging aid (the tier-1
suite exercises it on every run). ``chip_smoke.py`` asserts that the chip
takes the compiled path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.belief import GammaBelief
from ...core.moments import MomentCurves, interp_matrix, pack_belief
from ...core.processes import PopulationPriors
from .kernel import (ALIVE, BLOCK_D, N_COLS, moment_curves_agg_packed,
                     moment_curves_packed)


def _pack(bel: GammaBelief, cores, priors: PopulationPriors,
          alive=None) -> "tuple[jax.Array, int]":
    """[D, 16] packed parameter rows (padded to a BLOCK_D multiple).

    Filler rows carry benign parameters (ones) and ALIVE=0 so the aggregate
    variant's reduction ignores them.
    """
    p = pack_belief(bel, cores, priors)
    a = p.a
    delta = jnp.full_like(a, priors.delta)
    mask = (jnp.ones_like(a) if alive is None
            else alive.astype(jnp.float32))
    pad_col = jnp.zeros_like(a)
    cols = [p.a, p.b, p.cores, p.eu, p.eu2, p.el, p.es1, p.ess2, p.rh1, p.z1,
            p.rk, p.z2, p.e_mu_nu, delta, mask, pad_col]
    packed = jnp.stack(cols, axis=-1).astype(jnp.float32)  # [D, 16]
    d = packed.shape[0]
    pad = (-d) % BLOCK_D
    if pad:
        filler = jnp.ones((pad, N_COLS), jnp.float32)
        filler = filler.at[:, ALIVE].set(0.0)
        packed = jnp.concatenate([packed, filler], axis=0)
    return packed, d


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The kernels' ``interpret`` flag: an explicit value wins; ``None``
    means compiled on a TPU backend, interpreted on any other."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _grids(t_grid: jax.Array, d_points: int):
    tc, tau, w_mat = interp_matrix(t_grid.astype(jnp.float32), d_points)
    return tc[None, :], tau[None, :], w_mat


def moment_curves_kernel(bel: GammaBelief, cores: jax.Array,
                         t_grid: jax.Array, priors: PopulationPriors,
                         *, d_points: int = 32,
                         interpret: bool | None = None) -> MomentCurves:
    """Kernel-backed moment curves. bel fields/cores: [D]; t_grid: [N]."""
    interpret = resolve_interpret(interpret)
    params, d = _pack(bel, cores, priors)
    tc, tau, w_mat = _grids(t_grid, d_points)
    el, vl = moment_curves_packed(
        params, t_grid.astype(jnp.float32)[None, :], tc, tau, w_mat,
        nd=d_points, interpret=interpret)
    return MomentCurves(EL=el[:d], VL=vl[:d])


def aggregate_moment_curves_kernel(
        bel: GammaBelief, cores: jax.Array, alive: jax.Array,
        t_grid: jax.Array, priors: PopulationPriors, *, d_points: int = 32,
        interpret: bool | None = None) -> MomentCurves:
    """Aggregate (sum over alive slots) curves [N] via the fused kernel."""
    interpret = resolve_interpret(interpret)
    params, _ = _pack(bel, cores, priors, alive=alive)
    tc, tau, w_mat = _grids(t_grid, d_points)
    el, vl = moment_curves_agg_packed(
        params, t_grid.astype(jnp.float32)[None, :], tc, tau, w_mat,
        nd=d_points, interpret=interpret)
    return MomentCurves(EL=el[0], VL=vl[0])
