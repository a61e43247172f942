"""Open-loop admission traffic and the cluster's observed events, from a seed.

One generator serves every served cell. A cell's traffic file
(``bench/traffic/<name>.json``) sets how simulated time maps onto the wall
clock and how many windows fill the cluster before the measured window; its
configuration file (``bench/configs/<name>.json``) sets the deployment: the
priors, the arrival rate, the clusters and their slots.

What is drawn, all on the host with NumPy:

* per-window arrival counts: Poisson(rate * dt), uncapped, drawn once from
  the traffic file's fixed ``counts_seed`` and put in a new order by the
  run's seed, so every seed offers the same amount of work in set-up and in
  the timed window;
* each arrival's due time: uniform inside its window's wall period;
* each arrival: its true parameters ``(lam, mu, sig)`` from the priors, its
  ``1 + Poisson(sig)`` cores, and the provider's belief after observing the
  request (arXiv:1804.07571 §2.1, GLOBAL information model);
* each window's observed events, by ``World``: every deployment the cluster
  holds produces them from its own true parameters.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

FIELDS = ("core_deaths", "spont_death", "scaleout_cores", "n_scaleouts")


def rng(seed: int, stream: int, *more: int) -> np.random.Generator:
    """A NumPy generator for one stream of a run (any whole ``seed``)."""
    return np.random.default_rng((int(seed) % (1 << 64), stream) + more)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Wall-clock plan of one run: window ``w`` ticks at ``w / windows_per_s``
    seconds after the window start (negative for fill windows, which run in
    set-up); its arrivals are due at ``due_s[first[w]:first[w + 1]]``."""

    windows_per_s: float
    n_fill: int
    counts: np.ndarray       # [W] arrivals per window
    first: np.ndarray        # [W + 1] index of each window's first arrival
    due_s: np.ndarray        # [n] due time of each arrival, window-relative

    @property
    def n_windows(self) -> int:
        return len(self.counts)


def n_windows(traffic: dict, seconds: float) -> tuple[int, int]:
    """(fill windows, measured windows) for a window of ``seconds``: every
    window whose tick falls inside it, plus one so that requests due at the
    close still find a window open."""
    measured = int(math.ceil(seconds * traffic["windows_per_s"])) + 1
    return int(traffic["fill_windows"]), measured


def schedule(config: dict, traffic: dict, seed: int,
             seconds: float) -> Schedule:
    n_fill, n_meas = n_windows(traffic, seconds)
    n_win = n_fill + n_meas
    mean = config["arrival_rate_per_h"] * config["dt_h"]
    fixed = np.random.default_rng(traffic["counts_seed"])
    counts = fixed.poisson(mean, n_win).astype(np.int64)
    r = rng(seed, 0)
    # the fill's windows and the timed ones are put in order apart, and the
    # last (which ticks at the close) keeps its count: every seed times the
    # same requests
    timed = slice(n_fill, n_win - 1)
    counts[:n_fill] = r.permutation(counts[:n_fill])
    counts[timed] = r.permutation(counts[timed])
    first = np.concatenate([[0], np.cumsum(counts)])
    period = 1.0 / traffic["windows_per_s"]
    due = [(w - n_fill) * period + np.sort(r.uniform(0.0, period, n))
           for w, n in enumerate(counts)]
    return Schedule(windows_per_s=traffic["windows_per_s"], n_fill=n_fill,
                    counts=counts, first=first,
                    due_s=np.concatenate(due) if due else np.zeros(0))


def draw_arrivals(config: dict, n: int, seed: int) -> dict:
    """``n`` arrivals as float32 arrays: true ``lam``, ``mu``, ``sig``, the
    request's cores ``c0`` and the provider's belief ``mu_a`` ... ``sig_b``
    (the priors, with the request's ``c0 - 1`` extra cores observed as one
    scale-out size)."""
    pr = config["priors"]
    r = rng(seed, 1)
    lam = r.gamma(pr["lam_shape"], 1.0 / pr["lam_rate"], n)
    mu = r.gamma(pr["mu_shape"], 1.0 / pr["mu_rate"], n)
    sig = r.gamma(pr["sig_shape"], 1.0 / pr["sig_rate"], n)
    c0 = 1.0 + r.poisson(sig)
    full = lambda v: np.full(n, v)
    out = dict(lam=lam, mu=mu, sig=sig, c0=c0,
               mu_a=full(pr["mu_shape"]), mu_b=full(pr["mu_rate"]),
               lam_a=full(pr["lam_shape"]), lam_b=full(pr["lam_rate"]),
               sig_a=pr["sig_shape"] + (c0 - 1.0), sig_b=full(pr["sig_rate"] + 1.0))
    return {k: v.astype(np.float32) for k, v in out.items()}


def dense(ev: dict, shape: tuple) -> dict:
    """One window's events (``World.issued``) as ``shape`` arrays: float32
    counts and a boolean ``spont_death``."""
    out = {}
    for k in FIELDS:
        a = np.zeros(int(np.prod(shape)), bool if k == "spont_death"
                     else np.float32)
        a[ev["slots"]] = ev[k]
        out[k] = a.reshape(shape)
    return out


class DecisionLog:
    """The decisions made, in order: per request its window, its flush part
    and its verdict (window -1: not decided), and ``order[:n]``, the
    requests in the order recorded. Plain arrays, so that a long window
    leaves the garbage collector nothing to walk."""

    def __init__(self, n: int):
        self.window = np.full(n, -1, np.int64)
        self.part = np.full(n, -1, np.int64)
        self.admit = np.zeros(n, bool)
        self.order = np.full(n, -1, np.int64)
        self.n = 0

    def record(self, i: int, window: int, part: int, admit: bool) -> None:
        """One decision; one thread records at a time."""
        self.window[i], self.part[i], self.admit[i] = window, part, admit
        self.order[self.n] = i
        self.n += 1


class World:
    """The clusters as they run: each deployment admitted, in the slot that
    the configuration's placement rule gives it, producing its own events.

    Each window, every deployment held (true ``lam``, ``mu``, ``sig``;
    ``n`` cores) loses each core with probability 1 - exp(-mu dt), shuts
    down with probability 1 - exp(-delta mu dt) and asks for
    Poisson(lam mu^nu dt) scale-outs of ``k + Poisson(k sig)`` cores in all
    (arXiv:1804.07571 §2.1). The draws of window ``w`` come from a
    generator of their own, seeded by the run's seed and ``w``.

    Which deployment sits in which slot follows the decisions served, in
    the order served: a fleet routes each request of a flush part to the
    cluster with the least used share of its capacity (counting the part's
    requests routed before it), and the i-th admitted request of a cluster
    takes its i-th free slot. Events are applied as the cluster does:
    deaths clamped to the cores held, scale-outs granted in slot order
    while they fit.

    ``next_events(log)`` is called before each tick with the decisions
    recorded so far (a ``DecisionLog``). Decisions of the window before the
    last tick are all in it; the last window's may not be yet: those that
    come later are placed in the slots the cluster gave them and get no
    events in the window they were missed in, as the cluster, which saw
    none for them, also records.
    """

    def __init__(self, config: dict, arrivals: dict, seed: int):
        pr = config["priors"]
        dt = float(config["dt_h"])
        self.caps = np.asarray(config["capacities"], np.float64)
        self.shape = (len(self.caps), int(config["max_slots"]))
        self.seed = seed
        f64 = lambda k: np.asarray(arrivals[k], np.float64)
        mu, lam, self.sig, self.c0 = f64("mu"), f64("lam"), f64("sig"), f64("c0")
        self.p_die = -np.expm1(-mu * dt)
        self.p_stop = -np.expm1(-pr["delta"] * mu * dt)
        self.so_rate = lam * mu ** pr["nu"] * dt
        self.alive = np.zeros(self.shape, bool)
        self.cores = np.zeros(self.shape)
        self.who = np.full(self.shape, -1, np.int64)
        self.issued = []          # each tick's events, sparse
        self._pending = None      # issued, not yet applied here
        self._seen = 0
        self._part = None
        self._part_used = None

    @property
    def used(self) -> np.ndarray:
        return np.sum(self.cores * self.alive, axis=1)

    def next_events(self, log) -> dict:
        """The next tick's events as ``self.shape`` arrays (``dense``)."""
        t = self.settle(log)
        self._pending = self._draw(t + 1)
        self.issued.append(self._pending)
        return dense(self._pending, self.shape)

    def settle(self, log) -> int:
        """Apply the decisions logged since the last call and the last
        tick's events; returns that tick's index."""
        n = log.n
        new, self._seen = log.order[self._seen:n], n
        t = len(self.issued) - 1
        for i in new.tolist():
            w = int(log.window[i])
            if w == t:
                self._ingest()
            elif w != t - 1 or self._pending is None:
                raise RuntimeError(f"decision of window {w} reached the "
                                   f"world after tick {t}")
            self._decided(i, int(log.part[i]), bool(log.admit[i]))
        self._ingest()
        return t

    def _decided(self, i: int, part: int, admit: bool) -> None:
        c = 0
        if self.shape[0] > 1:
            if part != self._part:
                self._part, self._part_used = part, self.used
            c = int(np.argmin(self._part_used / self.caps))
            self._part_used[c] += self.c0[i]
        if not admit:
            return
        free = np.flatnonzero(~self.alive[c])
        if len(free):
            s = free[0]
            self.alive[c, s], self.cores[c, s], self.who[c, s] = \
                True, self.c0[i], i

    def _ingest(self) -> None:
        """Apply the pending events. They name only slots held when they
        were drawn; a deployment placed since has none."""
        ev, self._pending = self._pending, None
        if ev is None or not len(ev["slots"]):
            return
        slots = ev["slots"]
        cores = self.cores.ravel()[slots]
        cores = cores - np.minimum(ev["core_deaths"], cores)
        cores[ev["spont_death"]] = 0.0
        still = cores > 0.0
        self.alive.ravel()[slots] = still
        self.cores.ravel()[slots] = cores
        req = np.where(still, ev["scaleout_cores"], 0.0)
        n_s = self.shape[1]
        c = slots // n_s
        cum = np.cumsum(req)
        before = np.concatenate([[0.0], cum])[np.searchsorted(slots, c * n_s)]
        grant = self.used[c] + (cum - before) <= self.caps[c]
        self.cores.ravel()[slots] = cores + np.where(grant, req, 0.0)
        self.who.ravel()[slots[~still]] = -1

    def _draw(self, w: int) -> dict:
        slots = np.flatnonzero(self.alive)
        who = self.who.ravel()[slots]
        r = rng(self.seed, 2, w)
        deaths = r.binomial(self.cores.ravel()[slots].astype(np.int64),
                            self.p_die[who])
        stop = r.random(len(slots)) < self.p_stop[who]
        k = r.poisson(self.so_rate[who])
        so = k + r.poisson(k * self.sig[who])
        return {"slots": slots, "core_deaths": deaths, "spont_death": stop,
                "scaleout_cores": so, "n_scaleouts": k}
