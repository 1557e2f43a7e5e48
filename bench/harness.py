"""What every driver shares: the run's record, its outcome, and refusal.

A driver (``bench/drivers/<kind>.py``, found by the ``kind`` of a traffic
mix) builds the cell from the seed, warms up its shapes, runs the window
inside ``Run.window()``, then compares what the window's own calls produced
with the plain reference (``bench/reference.py``) and returns an
``Outcome``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import time
from typing import Dict

import jax
import numpy as np

from bench import trace as trace_lib

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot be measured here; no result line is printed."""


class Run:
    """What one run records: set-up time, compilations by phase, the
    window's bounds, device memory, the reduced trace, and what each driver
    leaves for the per-layer readers in ``ctx``."""

    def __init__(self, t_start: float, peak: dict, chips: int, trace_dir):
        self.t_start = t_start
        self.peak = peak
        self.chips = chips
        self.trace_dir = trace_dir
        self.phase = "setup"
        self.compile_s = {"setup": 0.0, "window": 0.0, "after": 0.0}
        self.compiles = {"setup": 0, "window": 0, "after": 0}
        self.setup_s = None
        self.window_s = None
        self.memory_peak_bytes = None
        self.trace = None
        self.ctx: Dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compile_s[self.phase] += duration
            self.compiles[self.phase] += 1

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def window(self):
        """The measured window.  Set-up ends where it starts, with one full
        garbage collection and the survivors frozen: the set-up's long-lived
        objects (compiled programs, their Python wrappers, the cell's
        tables) are left out of the window's collections, which still
        collect everything the window allocates.  With a trace directory the
        profiler records the window; device memory is read after it."""
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # the device and the runtime's own host events; no Python call
            # tracing, which would slow the host path that is measured
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        gc.collect()
        gc.freeze()
        pauses = []

        def on_gc(phase, info, t=[0.0]):
            if phase == "start":
                t[0] = time.perf_counter()
            else:
                pauses.append((info["generation"], time.perf_counter() - t[0]))

        gc.callbacks.append(on_gc)
        self.setup_s = time.perf_counter() - self.t_start
        self.phase = "window"
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
            yield
        self.window_s = time.perf_counter() - t0
        self.phase = "after"
        gc.callbacks.remove(on_gc)
        gc.unfreeze()
        if self.trace_dir:
            jax.profiler.stop_trace()
        stats = [d.memory_stats() or {} for d in jax.local_devices()[: self.chips]]
        self.memory_peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
        self.log(
            f"setup_s {self.setup_s:.3f} (compile {self.compile_s['setup']:.3f} s in "
            f"{self.compiles['setup']} programs); window {self.window_s:.3f} s, "
            f"compilations inside it: {self.compiles['window']}; garbage "
            f"collections {len(pauses)}, of generation 2 "
            f"{sum(g == 2 for g, _ in pauses)}, longest pause "
            f"{1e3 * max([d for _, d in pauses] or [0]):.3f} ms"
        )

    def reduce_trace(self) -> None:
        if self.trace_dir:
            self.trace = trace_lib.reduce_dir(self.trace_dir)


def span(name: str):
    """A host span the trace reducer labels idle gaps with."""
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    readings: Dict[str, float]

    def checks(self, limits: Dict[str, float]) -> Dict[str, dict]:
        missing = set(limits) ^ set(self.readings)
        if missing:
            raise KeyError(f"readings and limits disagree on {sorted(missing)}")
        return {name: {"value": self.readings[name], "limit": limits[name]}
                for name in sorted(limits)}


def free() -> None:
    """Release the program's device buffers before the reference runs."""
    gc.collect()


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (an infinite value stays infinite;
    no values read +inf)."""
    s = np.sort(np.asarray(values, np.float64))
    if not len(s):
        return float("inf")
    return float(s[max(math.ceil(q * len(s)) - 1, 0)])
