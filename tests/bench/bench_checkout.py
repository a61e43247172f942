"""A small checkout of the benchmark for its CPU tests: the real ``bench/``
files and program, one small served cell cut from ``region10``, and the
JAX settings the harness changes restored afterwards."""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: region10 cut to two clusters of 5,000 cores and 512 slots on an 8-point
#: grid; 40 fill windows bring them to where the policy starts to reject,
#: so a two-second window sees it admit and reject
SMALL = dict(capacities=[5000.0, 5000.0], max_slots=512,
             arrival_rate_per_h=4.0, max_arrivals=32, horizon_h=2160.0,
             grid=dict(t_min_h=6.0, t_max_h=2160.0, points=8, d_points=4))
TRAFFIC = dict(driver="served", windows_per_s=20.0, fill_windows=40,
               counts_seed=0, compared_windows=60)
CELL = "small_serve"


def small_config() -> dict:
    with open(os.path.join(BENCH, "configs", "region10.json"),
              encoding="utf-8") as f:
        config = json.load(f)
    config.update(SMALL, name="small")
    return config


def make_checkout(root: str, extra_per_layer=()) -> str:
    """Copy ``bench/`` into ``root``, link the program, and write a
    ``BENCHMARK.json`` whose one cell is the small one."""
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(root, "bench", "configs", "small.json"), "w",
              encoding="utf-8") as f:
        json.dump(small_config(), f)
    with open(os.path.join(root, "bench", "traffic", "small_steady.json"),
              "w", encoding="utf-8") as f:
        json.dump(TRAFFIC, f)
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "small", "source": "region10, cut",
                        "file": "bench/configs/small.json", "reduced": [],
                        "why": "CPU tests"}]
    spec["workloads"] = [{"name": CELL, "config": "small",
                          "traffic": "small_steady", "chips": 1,
                          "why": "CPU tests"}]
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            if "workloads" in m:
                m["workloads"] = [CELL]
    spec["per_layer"] += list(extra_per_layer)
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(spec, f)
    return root


@contextlib.contextmanager
def jax_settings_kept():
    """The harness points JAX's persistent cache at the checkout; put the
    process's own settings back for the tests that follow."""
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)


def run_small(root: str, seed: int, *, trace: int = 0, seconds: float = 2.0,
              engine_hook=None):
    import run

    args = run.parse(["--workload", CELL, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)])
    with jax_settings_kept():
        return run.run_cell(args, root=root, require_chip=False,
                            engine_hook=engine_hook)
