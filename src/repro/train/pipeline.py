"""Pipeline parallelism: GPipe microbatch streaming via shard_map +
collective_permute.

For scaling beyond the (pod, data, model) production mesh — e.g. 1000+ nodes
where a layer stack no longer fits a single pod's TP domain — the layer stack
is partitioned across a `stage` mesh axis and microbatches stream through the
stages; each tick every stage applies its layer chunk and ppermutes its
activation to the next stage. Differentiable end-to-end (jax transposes
ppermute automatically), so `jax.grad` of a pipelined loss just works.

Bubble fraction = (S-1)/(M+S-1) — choose M >> S. Off by default: the
production dry-run meshes carry DP/FSDP/TP/EP; this module is the documented
and tested PP option (tests/test_pipeline.py proves forward and gradient
equivalence with the sequential stack on a multi-device mesh).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def make_pipeline_forward(stage_fn: Callable, n_stages: int, mesh,
                          data_axis: str | None = "data"):
    """Build a pipelined forward over a stacked-parameter layer stack.

    stage_fn(params_chunk, x) -> x : applies one stage's layer chunk
      (params_chunk: [L/S, ...] pytree slice; x: [mb, ...] activation).
    Returns pipeline(params, x_mb) where params: [L, ...] stacked pytree
    (sharded over 'stage') and x_mb: [M, mb, ...] microbatches. Output:
    [M, mb, ...] (replicated over 'stage').
    """
    s = n_stages

    def inner(params_local, x_mb):
        stage = jax.lax.axis_index("stage")
        m = x_mb.shape[0]
        ticks = m + s - 1
        perm = [(i, (i + 1) % s) for i in range(s)]

        def tick(carry, t):
            recv, outputs = carry
            xin = jnp.where(stage == 0,
                            x_mb[jnp.clip(t, 0, m - 1)], recv)
            y = stage_fn(params_local, xin)
            recv_next = jax.lax.ppermute(y, "stage", perm)
            mb_idx = t - (s - 1)
            valid = (stage == s - 1) & (mb_idx >= 0) & (mb_idx < m)
            idx = jnp.clip(mb_idx, 0, m - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, idx, 0,
                                               keepdims=False)
            upd = jnp.where(valid, y, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(outputs, upd, idx, 0)
            return (recv_next, outputs), None

        outputs0 = jnp.zeros_like(x_mb)
        recv0 = jnp.zeros_like(x_mb[0])
        (_, outputs), _ = jax.lax.scan(tick, (recv0, outputs0),
                                       jnp.arange(ticks))
        # broadcast the last stage's outputs to every stage
        mask = (stage == s - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, "stage")

    dspec = (data_axis,) if data_axis and data_axis in mesh.axis_names else (None,)
    x_spec = P(None, *dspec, None, None)

    def pipeline(params, x_mb):
        param_specs = jax.tree.map(lambda _: P("stage"), params)
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(param_specs, x_spec),
            out_specs=x_spec,
            check_vma=False,
        )(params, x_mb)

    return pipeline


def sequential_reference(stage_fn: Callable, n_stages: int, params, x_mb):
    """Ground truth: apply all stages sequentially to each microbatch."""
    def apply_all(x):
        l = jax.tree.leaves(params)[0].shape[0]
        chunk = l // n_stages
        for si in range(n_stages):
            p = jax.tree.map(lambda a: a[si * chunk:(si + 1) * chunk], params)
            x = stage_fn(p, x)
        return x
    return jax.vmap(apply_all)(x_mb)
