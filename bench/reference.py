"""Plain reference of served admission, in NumPy, independent of the program.

The semantics it follows are those a served configuration states
(``bench/configs/<name>.json``) after arXiv:1804.07571 §§2-5:

* a cluster is a table of slots; each holds a deployment's cores and the
  provider's Gamma beliefs over its ``(mu, lam, sig)``;
* at each window's tick the aggregate moment curves (sum over alive slots
  of E[L_t] and V[L_t] on the horizon grid) are recomputed from the table
  as it stands, every ``agg_refresh_steps`` ticks, and then the window's
  observed events are applied: core deaths clamped to the cores held,
  shutdowns, scale-outs granted in slot order while they fit the capacity,
  and the conjugate belief updates;
* requests are decided in the flushes that decided them, in order. A fleet
  first routes each request of a flush to the cluster with the least used
  share of its capacity, counting the requests it already routed in that
  flush; each cluster then admits its requests one by one by the
  second-moment rule (Cantelli: for every horizon point, E <= c and
  V / (V + (c - E)^2) <= rho, and the request's cores fit now), placing the
  i-th admitted request in the i-th free slot and adding its curves to the
  aggregate.

``Replay`` carries that state in float64. Given the decisions a run served,
it follows them and reports how far each departs from the reference (a
served decision is the answer under test; the state follows it, so one
disagreement does not derail the rest). Without them it decides every
request itself, which is how ``bench/control.py`` puts it in the program's
place, its curves and aggregates rounded to a lower precision.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import numpy as np
from scipy.special import gammaln

BELIEF = ("mu_a", "mu_b", "lam_a", "lam_b", "sig_a", "sig_b")


def exact(x):
    return x


def rounding(dtype_name: str) -> Callable:
    """Round every intermediate to ``dtype_name`` (``float64``: no rounding)."""
    if dtype_name == "float64":
        return exact
    import ml_dtypes

    dt = {"bfloat16": ml_dtypes.bfloat16, "float32": np.float32,
          "float16": np.float16}[dtype_name]
    return lambda x: np.asarray(x).astype(dt).astype(np.float64)


def horizon_grid(spec: dict) -> np.ndarray:
    """The configuration's geometric horizon grid, in hours, as the float32
    values the configuration runs on."""
    return np.exp(np.linspace(math.log(spec["t_min_h"]), math.log(
        spec["t_max_h"]), spec["points"])).astype(np.float32).astype(np.float64)


def interp_weights(grid, w: float, d_points: int) -> np.ndarray:
    """``[ND + 1, N]`` weights of linear interpolation from the checkpoints
    ``0, w, ..., ND w`` (value 1 at 0) onto ``grid``."""
    xs = w * np.arange(d_points + 1)
    return np.stack([np.interp(grid, xs, row)
                     for row in np.eye(d_points + 1)])


def curves(bel: dict, cores, grid, priors: dict, d_points: int,
           q: Callable = exact):
    """E[L_t], V[L_t] of each deployment, ``[M, N]`` (paper Props. 2-3 in
    closed form; the zero-core death term on a uniform grid of ``d_points``
    midpoint checkpoints, interpolated linearly onto ``grid``)."""
    nu, delta = priors["nu"], priors["delta"]
    a = q(np.asarray(bel["mu_a"], np.float64))[:, None]
    b = q(np.asarray(bel["mu_b"], np.float64))[:, None]
    lam_a = q(np.asarray(bel["lam_a"], np.float64))
    lam_b = q(np.asarray(bel["lam_b"], np.float64))
    sig_a = q(np.asarray(bel["sig_a"], np.float64))
    sig_b = q(np.asarray(bel["sig_b"], np.float64))
    c = q(np.asarray(cores, np.float64))
    t = np.asarray(grid, np.float64)[None, :]

    el = q(lam_a / lam_b)
    el2 = q(lam_a * (lam_a + 1.0) / lam_b ** 2)
    es = q(sig_a / sig_b)
    es2 = q(sig_a * (sig_a + 1.0) / sig_b ** 2)
    e_s1, e_s1_sq, e_ss2 = q(es + 1.0), q(es2 + 2.0 * es + 1.0), q(es2 + 2.0 * es)
    eu, eu2 = q(el * e_s1), q(el2 * e_s1_sq)

    lg_a = q(gammaln(a))
    log_b = q(np.log(b))
    l1 = q(np.log1p(t / b))
    l2 = q(np.log1p(2.0 * t / b))

    def ratio(p):
        z = a + p
        z = np.where(np.abs(z) < 1e-12, 1e-12, z)
        return q(np.exp(q(gammaln(z + 1.0)) - lg_a - p * log_b) / z), q(z)

    r1, z1 = ratio(nu - 1.0)
    h1 = q(r1 * -np.expm1(-z1 * l1))
    h2 = q(r1 * -np.expm1(-z1 * l2))
    eq = q(eu[:, None] * h1)
    evq = q(el[:, None] * (e_s1[:, None] * h1 + 0.5 * e_ss2[:, None] * h2))
    r2, z2 = ratio(2.0 * nu - 2.0)
    kk = q(r2 * (-2.0 * np.expm1(-z2 * l1) + np.expm1(-z2 * l2)))
    veq = q(eu2[:, None] * kk - eq ** 2)
    vq = q(evq + np.maximum(veq, 0.0))

    p1 = q(np.exp(-a * l1))
    p2 = q(np.exp(-a * l2))
    ebn = q(c[:, None] * p1)
    vb = q(c[:, None] * (p1 - p2) + c[:, None] ** 2 * np.maximum(p2 - p1 ** 2, 0.0))
    em = q(np.exp(-a * np.log1p(delta * t / b)))
    vm = q(em * (1.0 - em))

    # zero-core death: Pr(not every core dead) on midpoint checkpoints
    e_mu_nu = q(np.exp(gammaln(a[:, 0] + nu) - lg_a[:, 0] - nu * log_b[:, 0]))
    w = float(grid[-1]) / d_points
    tau = w * (np.arange(d_points) + 0.5)
    p_lag = q(np.exp(-a * np.log1p(tau[None, :] / b)))
    s = q((eu * e_mu_nu * w)[:, None] * np.log1p(-np.minimum(p_lag, 1.0 - 1e-7)))
    tc = w * np.arange(1, d_points + 1)
    p_self = q(np.exp(-a * np.log1p(tc[None, :] / b)))
    log_dead = q(c[:, None] * np.log1p(-np.minimum(p_self, 1.0 - 1e-7))
                 + np.cumsum(s, axis=1))
    ed_sub = q(np.cumprod(q(-np.expm1(log_dead)), axis=1))
    ed_ext = np.concatenate([np.ones((len(c), 1)), ed_sub], axis=1)
    ed = q(ed_ext @ interp_weights(grid, w, d_points))
    vd = q(ed * (1.0 - ed))

    er, vr = q(eq + ebn), q(vq + vb)
    edr = q(ed * er)
    vdr = q(vd * vr + vd * er ** 2 + ed ** 2 * vr)
    return q(em * edr), q(vm * vdr + vm * edr ** 2 + em ** 2 * vdr)


class Verdict(NamedTuple):
    admit: bool
    fits: bool
    score: float       # max over the grid of the Cantelli mass (1 where E > c)


def second_moment(agg_el, agg_vl, cand_el, cand_vl, util, c0, cap,
                  rho) -> Verdict:
    el = agg_el + cand_el
    vl = agg_vl + cand_vl
    slack = np.maximum(cap - el, 0.0)
    mass = vl / (vl + slack ** 2 + 1e-30)
    score = float(np.max(np.where(el <= cap, mass, 1.0)))
    fits = bool(util + c0 <= cap)
    return Verdict(admit=fits and score <= rho, fits=fits, score=score)


class Replay:
    """Reference state of every cluster of one served configuration."""

    def __init__(self, config: dict, q: Callable = exact):
        self.cfg = config
        self.q = q
        self.caps = np.asarray(config["capacities"], np.float64)
        self.n_c, self.n_s = len(self.caps), int(config["max_slots"])
        self.rho = float(config["policy"]["rho"])
        self.dt = float(config["dt_h"])
        self.k = int(config["agg_refresh_steps"])
        self.priors = config["priors"]
        self.grid = horizon_grid(config["grid"])
        self.d_points = int(config["grid"]["d_points"])
        shape = (self.n_c, self.n_s)
        self.alive = np.zeros(shape, bool)
        self.cores = np.zeros(shape)
        pr = self.priors
        self.bel = {f: np.full(shape, float(pr[p])) for f, p in zip(
            BELIEF, ("mu_shape", "mu_rate", "lam_shape", "lam_rate",
                     "sig_shape", "sig_rate"))}
        self.util = np.zeros(self.n_c)
        self.agg = None          # (el [C, N], vl [C, N]) while one is held
        self.ticks = 0
        self.util_trace, self.fail_trace = [], []
        self.accepted = np.zeros(self.n_c)
        self.rejected = np.zeros(self.n_c)

    # -- ticks ------------------------------------------------------------
    def tick(self, events: dict, aggregate: bool) -> None:
        """Close the open window, refresh the aggregate if this tick does
        (only when ``aggregate``: a window whose decisions are not compared
        needs none) and apply the window's events."""
        if self.ticks:
            self.util_trace.append(self._used())
        if self.ticks % self.k == 0:
            self.agg = self._aggregate() if aggregate else None
        self._ingest(events)
        self.ticks += 1

    def finish(self) -> None:
        self.util_trace.append(self._used())

    def _used(self) -> np.ndarray:
        return np.sum(self.cores * self.alive, axis=1)

    def _aggregate(self):
        el = np.zeros((self.n_c, len(self.grid)))
        vl = np.zeros_like(el)
        for c in range(self.n_c):
            idx = np.flatnonzero(self.alive[c])
            if len(idx):
                e, v = curves({f: x[c, idx] for f, x in self.bel.items()},
                              self.cores[c, idx], self.grid, self.priors,
                              self.d_points, self.q)
                el[c], vl[c] = self.q(e.sum(0)), self.q(v.sum(0))
        return el, vl

    def _ingest(self, ev: dict) -> None:
        alive = self.alive
        alive_f = alive.astype(np.float64)
        deaths = np.minimum(ev["core_deaths"], self.cores) * alive_f
        exposure = self.cores * self.dt * alive_f
        cores = self.cores - deaths
        cores = np.where(ev["spont_death"] & alive, 0.0, cores)
        alive = alive & (cores > 0.0)
        alive_f = alive.astype(np.float64)
        req = ev["scaleout_cores"] * alive_f
        n_req = ev["n_scaleouts"] * alive_f
        used = np.sum(cores * alive_f, axis=1, keepdims=True)
        grant = used + np.cumsum(req, axis=1) <= self.caps[:, None]
        cores = cores + np.where(grant, req, 0.0)
        self.fail_trace.append(np.sum(np.where(grant, 0.0, n_req), axis=1))
        b, nu = self.bel, self.priors["nu"]
        b["mu_a"] = b["mu_a"] + deaths
        b["mu_b"] = b["mu_b"] + exposure
        live = np.nonzero(alive)
        ma, mb = b["mu_a"][live], b["mu_b"][live]
        e_mu_nu = np.exp(gammaln(ma + nu) - gammaln(ma) - nu * np.log(mb))
        b["lam_a"] = b["lam_a"] + n_req
        b["lam_b"][live] += e_mu_nu * self.dt
        b["sig_a"] = b["sig_a"] + (req - n_req)
        b["sig_b"] = b["sig_b"] + n_req
        self.alive, self.cores = alive, cores
        self.util = np.sum(cores * alive_f, axis=1)

    # -- decisions --------------------------------------------------------
    def route(self, c0: np.ndarray) -> np.ndarray:
        """Least used share of capacity, counting this flush's requests."""
        u = self.util.copy()
        out = np.zeros(len(c0), np.int64)
        for i, x in enumerate(c0):
            c = int(np.argmin(u / self.caps))
            out[i] = c
            u[c] += x
        return out

    def flush(self, arrivals: list, served=None) -> list:
        """Decide one flush of ``arrivals`` (dicts with ``c0`` and the belief
        fields). With ``served`` (the run's verdicts) the state follows them;
        without, it follows the reference's own. Returns the reference's
        ``Verdict`` of each request (``None`` where no aggregate is held)."""
        n = len(arrivals)
        c0 = np.asarray([a["c0"] for a in arrivals], np.float64)
        route = self.route(c0) if self.n_c > 1 else np.zeros(n, np.int64)
        cand = None
        if self.agg is not None and n:
            cand = curves({f: np.asarray([a[f] for a in arrivals])
                           for f in BELIEF}, c0, self.grid, self.priors,
                          self.d_points, self.q)
        verdicts, admitted = [], np.zeros(n, bool)
        run = {c: (None if self.agg is None else
                   (self.agg[0][c].copy(), self.agg[1][c].copy()),
                   self.util[c]) for c in set(route.tolist())}
        for i in range(n):
            c = int(route[i])
            agg, used = run[c]
            if agg is None:
                v = None
                ok = bool(served[i])
            else:
                v = second_moment(agg[0], agg[1], cand[0][i], cand[1][i],
                                  used, c0[i], self.caps[c], self.rho)
                ok = v.admit if served is None else bool(served[i])
            verdicts.append(v)
            if ok:
                admitted[i] = True
                if agg is not None:
                    agg = (agg[0] + cand[0][i], agg[1] + cand[1][i])
                run[c] = (agg, used + c0[i])
        self._place(arrivals, route, admitted, cand)
        return verdicts

    def _place(self, arrivals, route, admitted, cand) -> None:
        self.rejected += np.bincount(route[~admitted], minlength=self.n_c)
        for c in range(self.n_c):
            rows = np.flatnonzero(admitted & (route == c))
            if not len(rows):
                continue
            free = np.flatnonzero(~self.alive[c])
            placed = rows[:len(free)]
            for i, s in zip(placed, free):
                self.alive[c, s] = True
                self.cores[c, s] = arrivals[i]["c0"]
                for f in BELIEF:
                    self.bel[f][c, s] = arrivals[i][f]
            self.accepted[c] += len(rows)
            if self.agg is not None and len(placed):
                self.agg[0][c] = self.q(self.agg[0][c] + cand[0][placed].sum(0))
                self.agg[1][c] = self.q(self.agg[1][c] + cand[1][placed].sum(0))
        self.util = self._used()


def gap(verdict: Verdict, served: bool, rho: float) -> float:
    """How far the reference's score lies on the far side of the bound from
    a served decision that disagrees with it, as a share of the bound;
    0 where they agree. A disagreement on whether the request fits at all
    is not a matter of precision and reads as ``inf``."""
    if verdict.admit == served:
        return 0.0
    if served and not verdict.fits:
        return math.inf
    return abs(verdict.score - rho) / rho
