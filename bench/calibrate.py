#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \\
        --control-seeds 3 [--seconds 2]

For each of ``--seeds``: a run of the cell's driver with a short window,
which gives the sound program's readings (the lower reading is their
largest).  For the first ``--control-seeds`` of them, the driver's
``controls`` at the cell's own size: the lower-precision control in the
program's place, and the faults the cell can have, planted in the
reference, each against the plain reference (``bench/training.py``,
``bench/serving.py``).

Each reading is printed as one JSON line.  The benchmark's runs never call
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, os.path.dirname(BENCH))


def _emit(**fields):
    print(json.dumps(fields), flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=2.0)
    args = parser.parse_args()

    from bench import run as bench_run

    cell = bench_run.load_cell(args.workload)
    cfg, traffic = cell["config"], cell["traffic"]
    driver = bench_run.load_driver(traffic["kind"])
    driver.check(cfg, traffic)
    import jax

    device = bench_run.device_info(jax, cell["chips"])
    from repro.launch.cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for n, seed in enumerate(args.seeds):
        r = bench_run.Run(time.perf_counter(), device["peak"], cell["chips"], None)
        out = driver.drive(r, cfg, traffic, seed, args.seconds)
        _emit(seed=seed, reading="program", **out.readings)
        if n < args.control_seeds:
            for reading, gaps in driver.controls(cfg, traffic, seed).items():
                _emit(seed=seed, reading=reading, **gaps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
