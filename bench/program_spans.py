"""Device idle time attributed to the program's own host spans.

The program marks its layer boundaries with ``repro.*`` spans
(``src/repro/tracing.py``), which land in the profiler's trace on the
device's clock.  From the window's trace, :func:`attribute` takes the
device's idle holes exactly as ``trace.reduce`` does -- the gaps in the
union of the first TPU plane's "XLA Ops", clipped to the :data:`WINDOW`
span -- and gives every idle nanosecond to the innermost (shortest)
``repro.*`` span that covers it, splitting a hole where spans begin or end.
Idle that no such span covers is the rest.  It also counts each span name
whose start lies in the window.

The readers below divide a layer's idle time by the count of the span that
marks its unit of work (a launch, an epoch, a job); each returns None where
the trace holds no such span, as a program without the spans gives.
"""
from __future__ import annotations

import glob
import os

from bench import trace

PREFIX = "repro."
LAUNCH = "repro.serving.launch"
FETCH = ("repro.serving.fetch",)
DISPATCH = ("repro.serving.gather", LAUNCH, "repro.serving.topk")
EPOCH = "repro.trainer.epoch"
# the epoch and its children but calibrate, which runs once a job
EPOCH_WORK = (EPOCH, "repro.trainer.shuffle", "repro.trainer.step",
              "repro.trainer.sync", "repro.trainer.evaluate")
INIT = "repro.trainer.init"
JOB_WORK = (INIT, "repro.trainer.calibrate")


def attribute(planes) -> dict:
    """Idle seconds of the window by innermost ``repro.*`` span
    (``idle_s``), the idle no such span covers (``rest_s``), their sum
    (``idle_total_s``), and the count of each span name that starts in the
    window (``counts``)."""
    window, spans = None, []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"no {trace.WINDOW!r} span in the trace")
    lo, hi = window
    devices = trace._device_planes(planes)
    if not devices:
        raise ValueError("no TPU plane with an 'XLA Ops' line in the trace")
    ops = []
    for line in devices[0].lines:
        if line.name == "XLA Ops":
            for ev in line.events:
                s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
                if e > s:
                    ops.append((s, e))
    holes, cursor = [], lo
    for s, e in trace._union(ops) + [[hi, hi]]:
        if s > cursor:
            holes.append((cursor, s))
        cursor = max(cursor, e)

    idle, rest = {}, 0.0
    spans.sort()
    active, i = [], 0
    for a, b in holes:
        while i < len(spans) and spans[i][0] < b:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        cuts = sorted({a, b} | {t for s, e, _ in active for t in (s, e) if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            over = [sp for sp in active if sp[0] <= x and sp[1] >= y]
            if over:
                name = min(over, key=lambda sp: (sp[1] - sp[0], -sp[0]))[2]
                idle[name] = idle.get(name, 0.0) + (y - x) * 1e-9
            else:
                rest += (y - x) * 1e-9
    counts = {}
    for s, _, name in spans:
        if lo <= s < hi:
            counts[name] = counts.get(name, 0) + 1
    return {"idle_s": idle, "rest_s": rest,
            "idle_total_s": sum(b - a for a, b in holes) * 1e-9,
            "counts": counts}


def spans_of(run):
    """:func:`attribute` of the run's window trace, cached in ``run.ctx``;
    None without a trace."""
    if not run.trace_dir:
        return None
    if "program_spans" not in run.ctx:
        paths = glob.glob(os.path.join(run.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        run.ctx["program_spans"] = (
            attribute(list(trace.load(max(paths, key=os.path.getmtime))))
            if paths else None)
    return run.ctx["program_spans"]


def idle_ms_per(run, names, unit):
    """Idle ms under the spans ``names`` per ``unit`` span in the window, or
    None where the trace holds no ``unit`` span."""
    spans = spans_of(run)
    if spans is None or not spans["counts"].get(unit):
        return None
    idle = sum(spans["idle_s"].get(name, 0.0) for name in names)
    return 1e3 * idle / spans["counts"][unit]


def fetch_idle_ms(run):
    """Device idle under the engine's result fetch, per scoring launch."""
    return idle_ms_per(run, FETCH, LAUNCH)


def dispatch_idle_ms(run):
    """Device idle under the engine's gather, launch and ``topk``'s own
    time, per scoring launch."""
    return idle_ms_per(run, DISPATCH, LAUNCH)


def epoch_idle_ms(run):
    """Device idle under the trainer's epoch and its children but
    calibrate, per epoch."""
    return idle_ms_per(run, EPOCH_WORK, EPOCH)


def job_idle_ms(run):
    """Device idle under the trainer's construction and calibration, per
    trainer constructed in the window."""
    return idle_ms_per(run, JOB_WORK, INIT)
