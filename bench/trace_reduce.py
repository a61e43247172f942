"""Reduce a JAX profiler trace to device busy time, idle gaps and the device
time of each jitted program.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``. Each
TPU is a plane named ``/device:TPU:<n>``; on it, the lines ``XLA Ops`` and
``Async XLA Ops`` hold one event per operation that ran and the line
``XLA Modules`` one event per execution of a compiled program, named
``<module>(<id>)``. The host's
threads are lines of the plane ``/host:CPU``, where the program's
``annotate`` spans and the dispatches (``PjitFunction(...)``) appear.
Events carry a start and a duration in nanoseconds on one clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS, ASYNC_OPS, MODULES = "XLA Ops", "Async XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def find_xplane(profile_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return paths[-1]


def module_name(event_name: str) -> str:
    """``jit_fleet_decide(1234)`` -> ``jit_fleet_decide``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def _union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_spans(planes):
    spans = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _gap_causes(gaps, host, longest: int = 500):
    """Sum the ``longest`` idle gaps, each under the host span that covers
    most of it (the shortest such span on a tie): what the host was doing
    while the device waited."""
    import numpy as np

    out = collections.Counter()
    if not gaps:
        return out
    gaps = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:longest]
    if host:
        hs = np.asarray([h[0] for h in host], np.float64)
        he = np.asarray([h[1] for h in host], np.float64)
        length = he - hs
    for gs, ge in gaps:
        name = "(no host span)"
        if host:
            cover = np.minimum(he, ge) - np.maximum(hs, gs)
            best = cover.max()
            if best > 0:
                tied = np.flatnonzero(cover == best)
                name = host[int(tied[np.argmin(length[tied])])][2]
        out[name] += (ge - gs) * 1e-9
    return out


def reduce_planes(planes) -> dict:
    """Busy seconds (averaged over the devices that ran anything), per-module
    device time, the operations that took most time and the longest idle
    gaps by what the host was doing."""
    planes = list(planes)
    busy, n_dev = 0.0, 0
    modules = collections.defaultdict(lambda: [0, 0.0])
    ops = collections.Counter()
    gaps = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        op_events = (lines.get(OPS, []) + lines.get(ASYNC_OPS, [])
                     or lines.get(MODULES, []))
        if not op_events:
            continue
        n_dev += 1
        merged = _union((e.start_ns, e.start_ns + e.duration_ns)
                        for e in op_events)
        busy += sum(e - s for s, e in merged) * 1e-9
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                 if b[0] > a[1]]
        for e in lines.get(OPS, []) + lines.get(ASYNC_OPS, []):
            ops[e.name] += e.duration_ns * 1e-9
        for e in lines.get(MODULES, []):
            m = modules[module_name(e.name)]
            m[0] += 1
            m[1] += e.duration_ns * 1e-9
    if not n_dev:
        return {"n_devices": 0, "busy_s": 0.0, "modules": {},
                "device_ops": [], "idle_gaps": []}
    causes = _gap_causes(gaps, _host_spans(planes))
    return {
        "n_devices": n_dev,
        "busy_s": busy / n_dev,
        "modules": {k: {"n": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
        "device_ops": [[k, v] for k, v in ops.most_common(TOP)],
        "idle_gaps": [[k, v] for k, v in causes.most_common(TOP)],
    }


def reduce_dir(profile_dir: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(profile_dir))
    return reduce_planes(data.planes)


def module_stats(summary: dict, names) -> tuple[int, float]:
    """(executions, device seconds) of the modules named in ``names``."""
    n, s = 0, 0.0
    for name in names:
        m = summary["modules"].get(name)
        if m:
            n += m["n"]
            s += m["seconds"]
    return n, s
