"""Slot placement of accepted arrivals, against a plain NumPy reference.

``_place_arrivals`` puts the i-th accepted arrival into the i-th free slot
(in slot order) and counts accepted arrivals beyond the free slots as
overflow. These tests hold every slot leaf, ``slot_overflow`` and the
placed-arrival mask to a serial NumPy replay of that rule bit for bit (NaN
payloads, infinities and -0.0 included), both for one cluster and under the
fleet's ``vmap`` over a cluster axis with one shared arrival batch; and they
check that the fleet decide places without any gather over the slot axis.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AZURE_PRIORS, SECOND, fleet_policy, geometric_grid
from repro.core.belief import GammaBelief
from repro.core.processes import DeploymentParams
from repro.serve import OnlineAdmissionEngine
from repro.sim import FleetConfig, SimConfig
from repro.sim.core import ArrivalStream, SimState, _place_arrivals

S = 128                     # slots of each cluster
C = 3                       # clusters under the fleet's vmap
N_TENANTS = 7
CFG = SimConfig(n_tenants=N_TENANTS)
SCENARIOS = ("random", "all_full", "overflow", "no_accepts", "all_free")
SPECIALS = np.array([np.nan, np.inf, -np.inf, -0.0, 0.0], np.float32)


def _floats(rng, shape):
    """Random float32 values with NaN, +-inf and -0.0 scattered in."""
    x = rng.standard_normal(shape).astype(np.float32)
    where = rng.random(shape) < 0.2
    x[where] = rng.choice(SPECIALS, size=int(where.sum()))
    return x


def _masks(rng, scenario, n_arr):
    """(alive [S], accept [A]) for one cluster under ``scenario``."""
    if scenario == "all_full":
        alive = np.ones(S, bool)
    elif scenario == "all_free":
        alive = np.zeros(S, bool)
    elif scenario == "overflow":
        # fewer free slots than accepted arrivals (none at A = 1)
        alive = np.ones(S, bool)
        alive[rng.choice(S, size=n_arr // 2, replace=False)] = False
    else:
        alive = rng.random(S) < rng.uniform(0.2, 0.95)
    if scenario == "no_accepts":
        accept = np.zeros(n_arr, bool)
    elif scenario == "overflow":
        accept = np.ones(n_arr, bool)
    else:
        accept = rng.random(n_arr) < 0.6
    return alive, accept


def _state(rng, alive):
    lead = alive.shape
    return SimState(
        alive=jnp.asarray(alive), cores=jnp.asarray(_floats(rng, lead)),
        params=DeploymentParams(*(jnp.asarray(_floats(rng, lead))
                                  for _ in range(3))),
        bel=GammaBelief(*(jnp.asarray(_floats(rng, lead)) for _ in range(6))),
        core_hours=jnp.zeros(lead[:-1]), fail_requests=jnp.zeros(lead[:-1]),
        total_requests=jnp.zeros(lead[:-1]), arr_accepted=jnp.zeros(lead[:-1]),
        arr_rejected=jnp.zeros(lead[:-1]),
        slot_overflow=jnp.asarray(rng.integers(0, 5, lead[:-1]), jnp.float32),
        n_departed=jnp.zeros(lead[:-1]),
        tenant=jnp.asarray(rng.integers(-3, N_TENANTS, lead), jnp.int32))


def _stream(rng, n_arr):
    f = lambda: jnp.asarray(_floats(rng, (n_arr,)))
    bel = GammaBelief(*(f() for _ in range(6)))
    return ArrivalStream(
        params=DeploymentParams(f(), f(), f()), c0=f(), bel=bel, bel_alt=bel,
        n_arrivals=jnp.asarray(n_arr),
        tenant=jnp.asarray(rng.integers(0, N_TENANTS, n_arr), jnp.int32))


def _slot_leaves(tree):
    """(cores, lam, mu, sig, six belief leaves, tenant) of a state or stream."""
    first = tree.cores if isinstance(tree, SimState) else tree.c0
    return [first, *tree.params, *tree.bel, tree.tenant]


def _reference(state: SimState, accept, stream: ArrivalStream):
    """Serial replay of the rule: the i-th accepted arrival into the i-th
    free slot in slot order; accepted arrivals past the last free slot
    overflow. Returns (slot leaves, alive, overflow, placed_arrival)."""
    leaves = [np.array(x) for x in _slot_leaves(state)]
    new = [np.asarray(x) for x in _slot_leaves(stream)]
    alive = np.array(state.alive)
    overflow = np.float32(state.slot_overflow)
    free = list(np.flatnonzero(~alive))
    placed_arrival = np.zeros(accept.shape[0], bool)
    for a in np.flatnonzero(accept):
        if not free:
            overflow += np.float32(1.0)
            continue
        s = free.pop(0)
        for leaf, n in zip(leaves, new):
            leaf[s] = n[a]
        alive[s] = True
        placed_arrival[a] = True
    return leaves, alive, overflow, placed_arrival


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _assert_bit_equal(got_state, got_placed, want):
    leaves, alive, overflow, placed_arrival = want
    for i, (g, w) in enumerate(zip(_slot_leaves(got_state), leaves)):
        assert np.array_equal(_bits(g), _bits(w)), f"slot leaf {i}"
    assert np.array_equal(np.asarray(got_state.alive), alive)
    assert np.array_equal(_bits(np.float32(got_state.slot_overflow)),
                          _bits(overflow))
    assert np.array_equal(np.asarray(got_placed), placed_arrival)


_place = jax.jit(lambda st, acc, stream: _place_arrivals(st, acc, stream, CFG))
_place_fleet = jax.jit(jax.vmap(
    lambda st, acc, stream: _place_arrivals(st, acc, stream, CFG),
    in_axes=(0, 0, None)))


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("n_arr", [1, 8, 96])
def test_placement_matches_reference(n_arr, scenario):
    rng = np.random.default_rng([n_arr, SCENARIOS.index(scenario)])
    alive, accept = _masks(rng, scenario, n_arr)
    state, stream = _state(rng, alive), _stream(rng, n_arr)
    got_state, got_placed = _place(state, jnp.asarray(accept), stream)
    _assert_bit_equal(got_state, got_placed, _reference(state, accept, stream))


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("n_arr", [1, 8, 96])
def test_fleet_placement_matches_reference(n_arr, scenario):
    """One shared arrival batch, each cluster its own alive and accept
    masks (the fleet's routing hands each cluster a different subset)."""
    rng = np.random.default_rng([n_arr, SCENARIOS.index(scenario), C])
    masks = [_masks(rng, scenario, n_arr) for _ in range(C)]
    alive = np.stack([m[0] for m in masks])
    accept = np.stack([m[1] for m in masks])
    state, stream = _state(rng, alive), _stream(rng, n_arr)
    got_state, got_placed = _place_fleet(state, jnp.asarray(accept), stream)
    for c in range(C):
        take = lambda x, c=c: x if x is None else x[c]
        want = _reference(jax.tree.map(take, state), accept[c], stream)
        _assert_bit_equal(jax.tree.map(take, got_state), got_placed[c], want)


def test_fleet_decide_has_no_slot_gather():
    """The fleet decide (three clusters of 64 slots, width 8) lowers with no
    gather whose result carries the slot axis: placement is a select over
    the arrival axis, not a per-slot lookup into the arrival batch."""
    n_slots, width = 64, 8
    base = SimConfig(capacity=500.0, arrival_rate=0.3,
                     horizon_hours=6 * 24.0, dt=24.0, max_slots=n_slots,
                     max_arrivals=width, d_points=8, priors=AZURE_PRIORS,
                     agg_refresh_steps=1)
    fleet = FleetConfig(base=base, capacities=(300.0, 200.0, 250.0))
    grid = geometric_grid(24.0, 3 * 6 * 24.0, 12)
    pol = fleet_policy(SECOND, capacities=fleet.capacities, rho=0.05)
    eng = OnlineAdmissionEngine(fleet, grid, SECOND, pol, micro_batch=width)
    text = eng._j_decide.lower(eng.policy, eng._cs, eng._win,
                               jnp.asarray(eng._pad_part)).as_text()
    results = re.findall(r'"stablehlo\.gather".*->\s*tensor<([^>]*)>', text)
    assert text.count('"stablehlo.gather"') == len(results)
    slot_gathers = [r for r in results
                    if str(n_slots) in r.split("x")[:-1]]
    assert not slot_gathers, slot_gathers
