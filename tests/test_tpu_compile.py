"""Compile rehearsal for TPU v5e at paper scale, with no chip attached.

The TPU compiler is installed beside the CPU backend, so a program can be
lowered and compiled for a *described* ``v5e:2x2`` topology: what the chip's
compiler would refuse (a primitive Mosaic cannot lower, a block that breaks
the tiling, a program that does not fit) fails here, at no chip time. Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every pytest-xdist worker
imports this file. The persistent compilation cache is off for these
compiles: a TPU executable written to it cannot be read back on a CPU host.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.paper_cluster import PAPER_FULL
from repro.core import SECOND, geometric_grid, make_policy
from repro.kernels.moment_curves.kernel import (
    BLOCK_D, N_COLS, moment_curves_agg_packed, moment_curves_packed)
from repro.sim import draw_arrival_stream, make_admission_core, slot_mesh

D, N, ND = 8192, 48, 24
GRID = geometric_grid(PAPER_FULL.dt, 3 * PAPER_FULL.horizon_hours, N)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU library logs under /tmp unless told not to
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """Abstract arguments (shape, dtype, sharding) for ``lower``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


def _kernel_args(one_chip):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    return (f32(D, N_COLS), f32(1, N), f32(1, ND), f32(1, ND), f32(ND + 1, N))


@pytest.mark.parametrize("kernel", [moment_curves_packed,
                                    moment_curves_agg_packed],
                         ids=["per_slot", "aggregate"])
def test_moment_curve_kernels_compile_for_v5e(one_chip, kernel):
    assert D % BLOCK_D == 0
    compiled = kernel.lower(*_kernel_args(one_chip), nd=ND,
                            interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _core_args(core, sharding):
    cs = jax.eval_shape(core.init)
    stream = jax.eval_shape(
        lambda k: jax.tree.map(lambda x: x[0],
                               draw_arrival_stream(k, core.cfg)),
        jax.random.PRNGKey(0))
    pol = make_policy(SECOND, rho=0.112, capacity=core.cfg.capacity)
    a = core.cfg.max_arrivals
    return dict(
        cs=_shapes(cs, sharding), stream=_shapes(stream, sharding),
        policy=_shapes(pol, sharding),
        key=_shapes(jax.random.PRNGKey(0), sharding),
        util=jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding),
        valid=jax.ShapeDtypeStruct((a,), jnp.bool_, sharding=sharding))


def _lower_core_steps(core, args) -> tuple:
    """The core's jitted refresh, apply_events and decide, lowered."""
    def decide(policy, cs, util, stream_t, valid):
        return core.decide_batch(policy, cs, util, core.candidates(stream_t),
                                 stream_t, valid)

    return (jax.jit(core.refresh_aggregates).lower(args["cs"]),
            jax.jit(core.apply_events).lower(args["key"], args["cs"]),
            jax.jit(decide).lower(args["policy"], args["cs"], args["util"],
                                  args["stream"], args["valid"]))


def test_paper_full_core_steps_compile_for_one_v5e_chip(one_chip):
    core = make_admission_core(PAPER_FULL, GRID, SECOND)
    for lowered in _lower_core_steps(core, _core_args(core, one_chip)):
        mem = lowered.compile().memory_analysis()
        assert mem.argument_size_in_bytes < 16 * 2**30


def test_tenant_core_steps_compile_for_one_v5e_chip(one_chip):
    """The paper cluster with the Azure trace's 5,958 tenants: the tick's
    observed-events step with the tenant fold, and the decide with the
    lookup and the arrival fold."""
    cfg = PAPER_FULL._replace(n_tenants=5958)
    core = make_admission_core(cfg, GRID, SECOND)
    args = _core_args(core, one_chip)
    assert args["cs"].tenants.shape == (2, 5958, 6)
    ev = args["cs"].slots.cores
    events = dict(core_deaths=ev, spont_death=jax.ShapeDtypeStruct(
        ev.shape, jnp.bool_, sharding=one_chip), scaleout_cores=ev,
        n_scaleouts=ev)

    def observe(cs, events):
        from types import SimpleNamespace

        return core.apply_observed(cs, SimpleNamespace(**events))

    def decide(policy, cs, util, stream_t, valid):
        stream_t, informed = core.lookup(cs, stream_t)
        return core.decide_batch(policy, cs, util, core.candidates(stream_t),
                                 stream_t, valid), informed

    for lowered in (
            jax.jit(observe).lower(args["cs"], events),
            jax.jit(decide).lower(args["policy"], args["cs"], args["util"],
                                  args["stream"], args["valid"])):
        mem = lowered.compile().memory_analysis()
        assert mem.argument_size_in_bytes < 16 * 2**30


def test_core_contractions_are_full_f32():
    """A TPU runs an f32 matmul at its default precision on bf16-rounded
    operands; every contraction of the admission steps must ask for
    HIGHEST. Checked on the lowered program, so no chip is needed."""
    core = make_admission_core(PAPER_FULL, GRID, SECOND)
    dots = [line for lowered in _lower_core_steps(core, _core_args(core, None))
            for line in lowered.as_text().splitlines()
            if "dot_general" in line]
    assert dots
    assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots), dots


def test_sharded_refresh_compiles_on_v5e_2x2_mesh(topo):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = slot_mesh(4, topo.devices)
    core = make_admission_core(PAPER_FULL, GRID, SECOND, mesh=mesh)
    cs = jax.eval_shape(make_admission_core(PAPER_FULL, GRID, SECOND).init)
    spec = lambda x: P("slots") if x.shape[:1] == (PAPER_FULL.max_slots,) \
        else P()
    slots = jax.tree.map(
        lambda x: _shapes(x, NamedSharding(mesh, spec(x))), cs.slots)
    rep = NamedSharding(mesh, P())
    cs = cs._replace(slots=slots, agg_el=_shapes(cs.agg_el, rep),
                     agg_vl=_shapes(cs.agg_vl, rep))
    compiled = jax.jit(core.refresh_aggregates).lower(cs).compile()
    assert "all-gather" in compiled.as_text()
