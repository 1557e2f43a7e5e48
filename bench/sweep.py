#!/usr/bin/env python3
"""Rate sweep of an online cell: the driver at each offered rate in turn,
in one process, on the chip.  Each run logs the completed rate, the queue
depth by quarter of its window and its p99; the knee is the highest rate at
which the completed rate keeps up and the depth does not grow.

    python3 bench/sweep.py --workload ml25m_k128.online_top10 \\
        --seconds 10 --rates 1000 2000 4000
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


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rates", type=float, nargs="+", required=True)
    args = parser.parse_args()

    from bench import run as bench_run

    cell = bench_run.load_cell(args.workload)
    driver = bench_run.load_driver(cell["traffic"]["kind"])
    import jax

    device = bench_run.device_info(jax, cell["chips"])
    from repro.launch.cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for rate in args.rates:
        traffic = dict(cell["traffic"], rate_per_s=rate)
        run = bench_run.Run(time.perf_counter(), device["peak"], cell["chips"], None)
        out = driver.drive(run, cell["config"], traffic, args.seed, args.seconds)
        print(json.dumps({"rate_per_s": rate, "attempted": out.attempted,
                          "failed": out.failed, **out.end_to_end}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
