#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout on a machine with the TPUs the cell asks for.
Everything is found by name from ``BENCHMARK.json``: the cell's configuration
file, its traffic file (``bench/traffic/<traffic>.json``), the driver that
traffic names (``bench/drivers/<driver>.py``) and, with ``--trace 1``, a
reader for each per-layer metric of the cell (``bench/metrics/<name>.py``).
Adding a configuration, a traffic mix or a metric adds files and entries
and changes nothing here.

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result holds its
per-layer metrics, the device's busy and window seconds and a breakdown.
Each number compared against the reference is printed beside its limit, as
the last lines on standard error and under ``checks``, last in the result.
Where JAX finds no TPU, or fewer than the cell asks for, the command exits
with 1 and prints no result.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")
#: how long after the close a run waits for answers still due
ANSWER_WAIT_S = 60.0


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(spec: dict, name: str, root: str = ROOT) -> SimpleNamespace:
    """The workload ``name`` with its configuration and traffic, by name."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    return SimpleNamespace(workload=w, config=load_json(
        os.path.join(root, conf["file"])), traffic=traffic)


def metrics_for(spec: dict, name: str, kind: str) -> list:
    """The cell's ``end_to_end`` or ``per_layer`` metrics: those that list
    it, and those that list no cells."""
    return [m for m in spec[kind]
            if "workloads" not in m or name in m["workloads"]]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def run_cell(args, *, root: str = ROOT, require_chip: bool = True,
             engine_hook=None, t_start: float = T_START):
    """Run the cell; returns ``(exit code, result line or None)``. Tests
    pass ``require_chip=False`` (and may break the engine through
    ``engine_hook``) to drive the rest of a run on the CPU."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program under {src}; run from a checkout",
              file=sys.stderr)
        return 2, None
    if src not in sys.path:
        sys.path.insert(0, src)
    spec = benchmark(root)
    c = cell(spec, args.workload, root)
    jax = setup_jax()
    devices = jax.devices()
    chips = int(c.workload["chips"])
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1, None
    driver = load_module(os.path.join(root, "bench", "drivers",
                                      c.traffic["driver"] + ".py"))
    ctx = SimpleNamespace(config=c.config, traffic=c.traffic, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          chips=chips, t_start=t_start,
                          engine_hook=engine_hook,
                          answer_wait_s=ANSWER_WAIT_S)
    out = driver.run(ctx)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    line = {}
    if args.trace:
        import trace_reduce

        summary = trace_reduce.reduce_dir(out["profile_dir"])
        shutil.rmtree(out["profile_dir"], ignore_errors=True)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = out["trace_window_s"]
        layer = SimpleNamespace(trace=summary, window_s=out["trace_window_s"],
                                device_kind=dev.device_kind,
                                programs=load_json(os.path.join(
                                    root, "bench", "programs.json")),
                                **out["layer"])
        for m in metrics_for(spec, args.workload, "per_layer"):
            reader = load_module(os.path.join(root, "bench", "metrics",
                                              m["name"] + ".py"))
            value = reader.read(layer)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
        print(json.dumps({"trace_modules": summary["modules"]}), flush=True)
    else:
        for m in metrics_for(spec, args.workload, "end_to_end"):
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    checks = out["checks"]
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    print(json.dumps({"info": out["info"]}), flush=True)
    for name, v in checks.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device,
              **line, "checks": checks}
    return 0, result


def main(argv=None) -> int:
    args = parse(argv)
    code, result = run_cell(args)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
