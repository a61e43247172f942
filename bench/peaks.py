"""The peaks table (``bench/peaks.json``), keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> dict:
    """Published peaks of one chip of ``device_kind``; a device the table
    does not hold is an error, never a default."""
    with open(path, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(ops: float, nbytes: float, device_kind: str) -> tuple[float, str]:
    """(seconds, bound): the least time the chip could take for the work,
    the larger of ops over the compute peak and bytes over HBM bandwidth."""
    p = peaks(device_kind)
    t_ops, t_mem = ops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
