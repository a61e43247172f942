"""Plain reference of tenant-history admission, in NumPy, independent of the
program.

The semantics are ``reference.Replay``'s (``bench/reference.py``) with one
addition, which a tenant configuration (``bench/configs/<name>.json`` with
``tenants``) states: every request names a tenant, and the provider's belief
about it is its tenant's conjugate posterior from that tenant's admitted
deployments observed so far (arXiv:1804.07571 §6, with the tenant's history
as the pseudo observations):

* a table keeps, per tenant, the observations pooled over every deployment
  of the tenant that was placed: core deaths, core-hours of exposure, alive
  deployment-hours, scale-outs, sizes less one (the scale-outs' cores beyond
  one each, and each placed request's ``c0 - 1``) and size observations
  (the scale-outs and the placed requests);
* each window's observations of a slot (deaths clamped to the cores held,
  the cores held times ``dt``, ``dt`` if it is still alive, its scale-outs
  and their cores) go into its tenant's row as they go into its belief;
* a request's belief is the prior with its tenant's row added: ``mu`` shape
  plus deaths, rate plus exposure; ``lam`` shape plus scale-outs, rate plus
  E[mu^nu] (under that ``mu`` posterior) times the alive hours; ``sig``
  shape plus sizes less one, rate plus size observations; and then the
  request's own ``c0`` as one more size observation. Every request of a
  flush reads the table as it stood before the flush.

The table and the beliefs are float64 (SciPy's ``gammaln``, whose float64
difference keeps about 1e-10 of the ratio at the shapes a tenant reaches),
or with ``q`` every sum of the table and every intermediate of a belief
rounded as ``q`` rounds (``bench/control_tenant.py``).
"""
from __future__ import annotations

import numpy as np
from scipy.special import gammaln

import reference

#: the columns of a tenant row
COLUMNS = ("core_deaths", "exposure_core_hours", "alive_hours",
           "n_scaleouts", "size_minus1", "n_sizes")


class TenantReplay(reference.Replay):
    """``reference.Replay`` with a tenant table: ``flush`` takes requests
    as dicts with ``c0`` and ``tenant`` and forms their beliefs itself."""

    def __init__(self, config: dict, q=reference.exact):
        super().__init__(config, q)
        self.n_tenants = int(config["tenants"]["count"])
        self.table = np.zeros((self.n_tenants, len(COLUMNS)))
        self.tenant = np.full((self.n_c, self.n_s), -1, np.int64)

    def _ingest(self, ev: dict) -> None:
        alive = self.alive
        deaths = np.minimum(ev["core_deaths"], self.cores) * alive
        exposure = self.cores * self.dt * alive
        cores = np.where(ev["spont_death"] & alive, 0.0,
                         self.cores - deaths)
        still = alive & (cores > 0.0)
        n_req = ev["n_scaleouts"] * still
        req = ev["scaleout_cores"] * still
        held = np.nonzero(alive)
        tenant = self.tenant[held]
        for col, inc in enumerate((deaths, exposure, self.dt * still, n_req,
                                   req - n_req, n_req)):
            self.table[:, col] = self.q(self.table[:, col] + np.bincount(
                tenant, weights=inc[held], minlength=self.n_tenants))
        super()._ingest(ev)

    def belief(self, tenant: int, c0: float) -> dict:
        """The belief fields (``reference.BELIEF``) of a request of
        ``tenant`` with ``c0`` cores, from the table as it stands."""
        pr, q = self.priors, self.q
        row = self.table[tenant]
        mu_a = q(pr["mu_shape"] + row[0])
        mu_b = q(pr["mu_rate"] + row[1])
        nu = pr["nu"]
        e_mu_nu = q(np.exp(gammaln(mu_a + nu) - gammaln(mu_a)
                           - nu * np.log(mu_b)))
        return {"mu_a": mu_a, "mu_b": mu_b,
                "lam_a": q(pr["lam_shape"] + row[3]),
                "lam_b": q(pr["lam_rate"] + q(e_mu_nu * row[2])),
                "sig_a": q(q(pr["sig_shape"] + row[4]) + (c0 - 1.0)),
                "sig_b": q(pr["sig_rate"] + row[5] + 1.0)}

    def flush(self, arrivals: list, served=None) -> list:
        full = [dict(a, **self.belief(int(a["tenant"]), float(a["c0"])))
                for a in arrivals]
        return super().flush(full, served=served)

    def _place(self, arrivals, route, admitted, cand) -> None:
        for c in range(self.n_c):
            rows = np.flatnonzero(admitted & (route == c))
            free = np.flatnonzero(~self.alive[c])
            for i, s in zip(rows[:len(free)], free):
                t = int(arrivals[i]["tenant"])
                self.tenant[c, s] = t
                self.table[t, 4] = self.q(self.table[t, 4]
                                          + arrivals[i]["c0"] - 1.0)
                self.table[t, 5] = self.q(self.table[t, 5] + 1.0)
        super()._place(arrivals, route, admitted, cand)
