"""The served engine's host phases over a window, from two of its
``metrics_snapshot()["engine"]`` dicts: one taken before the window and one
after. A counter that the program does not keep is left out, so a program
without these counters gives an empty result and nothing raises."""
from __future__ import annotations

#: per-request and per-decide histograms: the mean of each, in ms
MEANS_MS = (("queue_wait_ms", "queue_wait_seconds"),
            ("answer_ms", "answer_seconds"),
            ("decide_wait_ms", "decide_wait_seconds"),
            ("tick_host_ms", "tick_host_seconds"))


def _delta(before: dict, after: dict, name: str):
    """(observations, sum) that the histogram ``name`` gained, or None."""
    a, b = after.get(name), before.get(name)
    if a is None or b is None:
        return None
    return a.total - b.total, a.sum - b.sum


def phases(before: dict, after: dict) -> dict:
    """Mean queue wait and answer time per request, decide wait and the
    rest of the part's host time per flush part, tick lock hold per tick
    and lock wait per acquire of each taker, in ms; the flush thread's busy
    share of the interval, in %."""
    out = {}
    for key, name in MEANS_MS:
        d = _delta(before, after, name)
        if d and d[0]:
            out[key] = 1e3 * d[1] / d[0]
    parts = _delta(before, after, "flush_batch_size")
    if parts and parts[0] and "part_host_seconds" in after \
            and "part_host_seconds" in before:
        out["part_host_ms"] = 1e3 * (after["part_host_seconds"]
                                     - before["part_host_seconds"]) / parts[0]
    waits_a = after.get("lock_wait_seconds")
    waits_b = before.get("lock_wait_seconds")
    if waits_a and waits_b:
        for taker, a in sorted(waits_a.items()):
            b = waits_b.get(taker, {"sum": 0.0, "count": 0})
            n = a["count"] - b["count"]
            if n:
                suffix = "" if taker == "flush" else "_" + taker
                out["lock_wait_ms" + suffix] = 1e3 * (a["sum"] - b["sum"]) / n
    keys = ("pump_busy_seconds", "time_s")
    if all(k in after and k in before for k in keys):
        span = after["time_s"] - before["time_s"]
        if span > 0.0:
            out["flush_busy_pct"] = 100.0 * (after["pump_busy_seconds"]
                                             - before["pump_busy_seconds"]) / span
    return out


def compiled(before: dict, after: dict) -> dict:
    """Programs each jitted step compiled between the two snapshots."""
    a, b = after.get("compiled_programs"), before.get("compiled_programs")
    if a is None or b is None:
        return {}
    return {step: n - b.get(step, 0) for step, n in sorted(a.items())}
