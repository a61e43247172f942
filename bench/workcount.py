"""Operations and bytes of one aggregate refresh, from its shapes alone.

The refresh evaluates each slot's moment curves E[L_t], V[L_t] on the
horizon grid and sums them over alive slots (``core/moments.py``; the
fused XLA lane, the Pallas kernel and the per-slot reference all compute
this). The count depends on the shapes only, never on which lane does the
work, so a change of lane keeps the yardstick. Elementwise arithmetic and
transcendentals count one operation each, a multiply-add two.

Per slot and grid point (``_curves_from_packed``): the two ``log1p`` terms
(5), the Q factor's H and K integrals, moments and clamp (30), the B and M
factors (22), D's variance and the composition of L = M D (Q + B) with its
variance (21), and the masked sums of E and V (4): ``PER_POINT`` = 82. Per
slot and zero-core checkpoint (``_d_curve_uniform``): ``PER_CHECKPOINT`` =
19. The interpolation onto the grid is a
``[ND + 1] x [N]`` matrix product per slot (2 (ND + 1) N). Packing the
beliefs (``pack_belief``: moments, five log-gamma terms): ``PER_SLOT`` = 40.

Bytes: the slot table read once (six belief fields, cores and the alive
mask, 4 bytes each) and the two curves written, per cluster.
"""
from __future__ import annotations

PER_POINT = 82
PER_CHECKPOINT = 19
PER_SLOT = 40
SLOT_BYTES = 8 * 4


def aggregate_work(clusters: int, slots: int, points: int,
                   d_points: int) -> tuple[float, float]:
    """(operations, bytes) of refreshing ``clusters`` aggregates of
    ``slots`` slots on ``points`` grid points and ``d_points`` checkpoints."""
    per_slot = (PER_POINT * points + PER_CHECKPOINT * d_points
                + 2 * (d_points + 1) * points + PER_SLOT)
    ops = float(clusters) * slots * per_slot
    nbytes = float(clusters) * (slots * SLOT_BYTES + 2 * points * 4)
    return ops, nbytes


def for_config(config: dict) -> tuple[float, float]:
    """``aggregate_work`` of a configuration file's shapes; its aggregate
    lane (``agg_backend``) does not enter the count."""
    return aggregate_work(len(config["capacities"]), config["max_slots"],
                          config["grid"]["points"], config["grid"]["d_points"])
