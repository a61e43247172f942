"""Kernel/algorithm microbenchmarks (wall time on whatever backend runs them;
rows from a CPU run are not chip numbers).

Measures the beyond-paper algorithmic wins:
  * continuous O(N) moment curves vs the paper's 5x600-step discrete cascade
  * vectorized policy evaluation throughput (deployments x horizon per sec)
Plus the Pallas moment-curve kernel, compiled on a TPU and interpreted
elsewhere; its row says which (an interpreted time is not a perf number).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AZURE_PRIORS, belief_from_prior, geometric_grid
from repro.core.moments import (aggregate_moment_curves, moment_curves,
                                moment_curves_discrete)

from .common import SCALES, csv_row, grid_for, sim_config


def _timeit(fn, *args, n=5):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e6  # us


def _sim_loop_rows(n_steps: int = 96, reps: int = 5) -> list:
    """Steps/sec of the 'quick'-preset simulator hot loop, second-moment
    policy — an *aggregation ablation*: per-slot aggregate recomputed from
    all slots every step (agg_backend=reference, refresh=1, the seed's
    aggregation strategy) vs the fused-aggregate fast path (blocked refresh
    + incremental candidate folding). Both lanes share the rest of this
    codebase's loop (hybrid samplers, vectorized placement), so the ratio
    isolates the aggregation/refresh change; the seed loop was additionally
    slower in those shared parts. The horizon is truncated to ``n_steps``
    steps so the benchmark stays CPU-friendly; per-step shapes (slot array,
    grid, arrival stream) are exactly the preset's.
    """
    from repro.core import SECOND, make_policy
    from repro.sim import AGG_REFERENCE, make_run

    scale = SCALES["quick"]
    base = sim_config(scale, horizon_hours=n_steps * scale.dt)
    grid = grid_for(scale, base)
    pol = make_policy(SECOND, rho=0.1, capacity=base.capacity)

    def steps_per_sec(cfg):
        run_fn = make_run(cfg, grid, SECOND)
        jax.block_until_ready(run_fn(jax.random.PRNGKey(0), pol))  # compile
        best = float("inf")
        for i in range(reps):
            t0 = time.time()
            jax.block_until_ready(run_fn(jax.random.PRNGKey(1 + i), pol))
            best = min(best, time.time() - t0)  # ambient load only slows runs
        return cfg.n_steps / best

    sps_ref = steps_per_sec(base._replace(agg_backend=AGG_REFERENCE,
                                          agg_refresh_steps=1))
    sps_fast = steps_per_sec(base)
    return [
        csv_row("sim/quick_loop_per_slot_recompute", 1e6 / sps_ref,
                f"steps_per_s={sps_ref:.1f} agg=reference refresh=1 "
                "(aggregation ablation baseline)"),
        csv_row("sim/quick_loop_fused_aggregate", 1e6 / sps_fast,
                f"steps_per_s={sps_fast:.1f} agg=fused "
                f"refresh={base.agg_refresh_steps} "
                f"speedup_vs_per_slot_recompute={sps_fast / sps_ref:.2f}x"),
    ]


def run(scale_name: str = "tiny", seed: int = 0) -> list:
    rows = []
    d = 1024
    bel = belief_from_prior(AZURE_PRIORS, (d,))
    cores = jnp.full((d,), 5.0)
    grid = geometric_grid(6.0, 3 * 365 * 24.0, 48)

    cont = jax.jit(lambda b, c: moment_curves(b, c, grid, AZURE_PRIORS,
                                              d_points=32))
    us_cont = _timeit(cont, bel, cores)
    rows.append(csv_row("kernels/moment_curves_continuous_jnp", us_cont,
                        f"D={d} N=48 curves_per_s={d / (us_cont/1e6):.3g}"))

    # paper-faithful cascade: 5 horizons x 600 uniform steps
    disc = jax.jit(lambda b, c: [
        moment_curves_discrete(b, c, 600, h / 600, AZURE_PRIORS)
        for h in (24.0, 168.0, 720.0, 8760.0, 26280.0)])
    us_disc = _timeit(disc, bel, cores, n=2)
    rows.append(csv_row("kernels/moment_curves_paper_cascade", us_disc,
                        f"D={d} 5x600steps speedup_vs_continuous="
                        f"{us_disc / us_cont:.1f}x"))

    from repro.kernels.moment_curves.ops import (moment_curves_kernel,
                                                 resolve_interpret)
    interpret = resolve_interpret()
    kern = jax.jit(lambda b, c: moment_curves_kernel(
        b, c, grid, AZURE_PRIORS, d_points=32, interpret=interpret))
    us_kern = _timeit(kern, bel, cores, n=2)
    mode = "interpret" if interpret else "compiled"
    rows.append(csv_row(f"kernels/moment_curves_pallas_{mode}", us_kern,
                        f"D={d} N=48 platform={jax.default_backend()}"))

    # fused-aggregate curves: masked sum over alive slots, no [S, N]
    # intermediate, vs the per-slot reference path summed outside
    alive = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5, (d,))
    ref_agg = jax.jit(lambda b, c, al: jax.tree.map(
        lambda x: jnp.sum(x * al.astype(jnp.float32)[:, None], 0),
        moment_curves(b, c, grid, AZURE_PRIORS, d_points=32)))
    us_ref_agg = _timeit(ref_agg, bel, cores, alive)
    fus_agg = jax.jit(lambda b, c, al: aggregate_moment_curves(
        b, c, al, grid, AZURE_PRIORS, d_points=32))
    us_fus_agg = _timeit(fus_agg, bel, cores, alive)
    rows.append(csv_row("kernels/aggregate_moment_curves_fused", us_fus_agg,
                        f"D={d} N=48 vs_per_slot_reference="
                        f"{us_ref_agg / us_fus_agg:.2f}x"))

    rows.extend(_sim_loop_rows())

    from repro.kernels.flash_attention.ref import attention_ref
    b, s, h, kvh, dh = 1, 1024, 8, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kvh, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kvh, dh), jnp.bfloat16)
    ref = jax.jit(lambda q, k, v: attention_ref(q, k, v, causal=True))
    us_ref = _timeit(ref, q, k, v, n=3)
    flops = 4 * b * h * s * s * dh / 2
    rows.append(csv_row("kernels/attention_ref_cpu", us_ref,
                        f"s={s} gflops={flops/1e9:.1f} "
                        f"cpu_gflops_s={flops / (us_ref/1e6) / 1e9:.1f}"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
