#!/usr/bin/env python3
"""Faults planted in a tenant cell's timed path, each of which the
comparison that decides ``correct`` must catch.

    python3 bench/faults_tenant.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 [--faults tenant_fold_dropped tenant_lookup_shifted ...]

The command is ``bench/faults.py``'s, with ``tenant_fold_dropped`` (caught
by the tenant table and the decisions) and ``tenant_lookup_shifted``
(caught by the decisions alone) among the faults it can plant; their
readings are in ``PERF.md``.
"""
from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def tenant_fold_dropped(engine):
    """The tick never folds into the tenant table and leaves it empty, so
    every lookup is cold: a request's belief is the population prior (but
    for the sizes its tenant placed since the tick)."""
    import jax.numpy as jnp

    ingest = engine._j_ingest

    def dropped(caps, cs, xs):
        cs, out = ingest(caps, cs, xs)
        return cs._replace(tenants=jnp.zeros_like(cs.tenants)), out

    engine._j_ingest = dropped


def tenant_lookup_shifted(engine):
    """Each request's belief is formed from the next tenant's row (tenant
    t reads row t + 1), while the fold, the placed slots' tenants and so
    the table stay as they should: only the decision shows the fault."""
    core = engine.core
    n = engine.base.n_tenants

    def shifted(cs, stream_t):
        looked, informed = core.lookup(
            cs, stream_t._replace(tenant=(stream_t.tenant + 1) % n))
        return looked._replace(tenant=stream_t.tenant), informed

    engine.core = core._replace(lookup=shifted)
    engine._build_jit()


def main(argv=None) -> int:
    import faults

    for fault in (tenant_fold_dropped, tenant_lookup_shifted):
        faults.FAULTS[fault.__name__] = fault
    return faults.main(argv)


if __name__ == "__main__":
    sys.exit(main())
