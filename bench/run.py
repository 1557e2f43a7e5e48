#!/usr/bin/env python3
"""On-chip benchmark of DP-MF: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``), compared against the limits in
``bench/limits/<cell>.json``.  The mix's ``kind`` names its driver,
``bench/drivers/<kind>.py``; per-layer metrics are read by
``bench/metrics/<metric>.py``.  Everything is found by name, so a new cell,
mix, driver or metric is new files and entries, not an edit.

The run builds the cell from the seed, warms up the cell's own shapes
(``setup_s``), measures for ``--seconds``, compares what the timed path
produced with the plain reference in ``bench/reference.py``, and prints one
JSON line last on standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``), and ``checks`` last: each number
compared, with its limit.  The same numbers end standard error.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  It refuses (exit 3, no result line) any platform but TPU, a
device kind missing from ``bench/peaks.json``, or fewer chips than the cell
asks for, or a configuration or mix its driver cannot check.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")
for _path in (ROOT, os.path.join(ROOT, "src")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from bench.harness import Refused, Run  # noqa: E402


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell ``name`` as BENCHMARK.json and its files describe it."""
    spec = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def for_cell(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": cell["chips"],
        "config": _load_json(ROOT, config["file"]),
        "traffic": _load_json(BENCH, "traffic", cell["traffic"] + ".json"),
        "limits": _load_json(BENCH, "limits", name + ".json"),
        "end_to_end": [m for m in spec["end_to_end"] if for_cell(m)],
        "per_layer": [m for m in spec["per_layer"] if for_cell(m)],
    }


def _load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no file bench/{kind}/{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """The reader of a per-layer metric: ``read(run)`` -> number or None."""
    return _load_module("metrics", metric).read


def load_driver(kind: str):
    """The driver of a traffic kind: ``check(cfg, traffic)`` refuses what it
    cannot compare, ``drive(run, cfg, traffic, seed, seconds)`` makes a
    run's ``Outcome``, ``controls(cfg, traffic, seed)`` reads the control and
    the planted faults for calibration."""
    return _load_module("drivers", kind)


def device_info(jax, chips: int) -> dict:
    """The device as JAX reports it; refuses what the benchmark cannot
    measure."""
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    peaks = _load_json(BENCH, "peaks.json")
    if platform != "tpu":
        raise Refused(f"platform {platform!r} is not a TPU")
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return {"platform": platform, "kind": kind, "count": len(devices),
            "peak": peaks[kind]}


def result_line(cell: dict, run, outcome, device: dict, trace: bool) -> dict:
    checks = outcome.checks(cell["limits"])
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if trace:
        metrics = {}
        for m in cell["per_layer"]:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(outcome.end_to_end, setup_s=run.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = run.memory_peak_bytes
    line = {"correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": dev}
    if trace and run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    try:
        cell = load_cell(args.workload)
        driver = load_driver(cell["traffic"]["kind"])
        driver.check(cell["config"], cell["traffic"])
        import jax

        device = device_info(jax, cell["chips"])
    except Refused as exc:
        print(f"refused: {exc}", file=sys.stderr)
        return 3

    from repro.launch.cache import configure_compile_cache

    cache = configure_compile_cache()
    # cache every program, so that only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"device {device['kind']} x{device['count']}, jax {jax.__version__}, "
          f"compile cache {cache}", file=sys.stderr)

    trace_dir = os.path.join(OUT, "trace", cell["name"]) if args.trace else None
    run = Run(T_START, device["peak"], cell["chips"], trace_dir)
    outcome = driver.drive(run, cell["config"], cell["traffic"], args.seed, args.seconds)
    line = result_line(cell, run, outcome, device, bool(args.trace))
    for name, check in line["checks"].items():
        print(f"check {name}: {check['value']!r} (limit {check['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
