"""Reduce a profiler trace of the measured window to device metrics.

The run wraps its window in a host span named :data:`WINDOW` and its own
calls into the program in host spans named ``bench.*``.  From the
``.xplane.pb`` that ``jax.profiler`` writes, :func:`reduce` takes

* the window: the :data:`WINDOW` span on the host;
* busy time: the union of the intervals in which an operation ran on each
  TPU core (its "XLA Ops" line), clipped to the window, averaged over the
  cores;
* device time per program ("XLA Modules" line) and per operation, each
  summed over the window, and each operation's self time (less the
  operations nested in it, as a loop's body is in the loop) for the
  breakdown;
* idle gaps: the stretches of the window in which the first core ran
  nothing, each labelled with the innermost ``bench.*`` span and the
  innermost other host event that cover its middle, summed by label.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
TOPK_KERNEL = "pruned_topk"   # the Pallas kernel's name in the trace
TOP = 10                      # entries of each breakdown list

_SUFFIX = re.compile(r"\(\d+\)$")


def _name(name: str) -> str:
    """A program's name without the run-specific id JAX appends."""
    return _SUFFIX.sub("", name)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(start, end, lo, hi):
    return max(start, lo), min(end, hi)


def _device_planes(planes):
    return [p for p in planes
            if p.name.startswith("/device:TPU:") and any(
                line.name == "XLA Ops" for line in p.lines)]


def reduce(planes, span_prefix: str = "bench.") -> dict:
    """Device busy and idle, per-program and per-operation device time, and
    labelled idle gaps of the :data:`WINDOW` span of a trace's planes."""
    host = [p for p in planes if p.name.startswith("/host:")]
    window = None
    spans, others = [], []
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if ev.name == WINDOW:
                    window = iv[:2]
                elif ev.name.startswith(span_prefix):
                    spans.append(iv)
                else:
                    others.append(iv)
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = window
    devices = _device_planes(planes)
    if not devices:
        raise ValueError("no TPU plane with an 'XLA Ops' line in the trace")

    busy, first_busy = [], None
    modules, ops, self_s = {}, {}, {}
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            table = ops if line.name == "XLA Ops" else modules
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if e <= s:
                    continue
                entry = table.setdefault(_name(ev.name), [0, 0.0])
                entry[0] += 1
                entry[1] += (e - s) * 1e-9
                if line.name == "XLA Ops":
                    intervals.append((s, e, ev.name))
        _self_times(intervals, self_s)
        merged = _union([iv[:2] for iv in intervals])
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged

    holes = []
    cursor = lo
    for s, e in first_busy + [[hi, hi]]:
        if s > cursor:
            holes.append((cursor, s))
        cursor = max(cursor, e)
    gaps = {}
    mids = [(a + b) // 2 for a, b in holes]
    for (a, b), outer, inner in zip(holes, _innermost(mids, spans), _innermost(mids, others)):
        label = outer or "no bench span"
        if inner is not None:
            label = f"{label} > {inner}"
        gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9

    def top(table):
        return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "modules": modules,
        "ops": ops,
        "device_ops": top({_short(k): v for k, v in self_s.items()}),
        "idle_gaps": top(gaps),
    }


def _short(name: str) -> str:
    """An operation's HLO text, cut to its result and the start of its
    operation (the whole text runs to kilobytes)."""
    return name if len(name) <= 120 else name[:117] + "..."


def _self_times(intervals, out: dict) -> None:
    """Add each operation's self time -- its duration less that of the
    operations nested in it, as a loop's body is in the loop -- to
    ``out`` by name, in seconds."""
    stack = []   # [end, name, self ns] of the enclosing operations

    def close():
        end, name, own = stack.pop()
        out[name] = out.get(name, 0.0) + own * 1e-9

    for s, e, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close()


def _innermost(times, intervals):
    """For each of the sorted ``times``, the name of the shortest interval
    that covers it, or None: one sweep over the intervals by start."""
    intervals = sorted(intervals)
    out, active, i = [], [], 0
    for t in times:
        while i < len(intervals) and intervals[i][0] <= t:
            active.append(intervals[i])
            i += 1
        active = [iv for iv in active if iv[1] > t]
        best = min(active, key=lambda iv: iv[1] - iv[0], default=None)
        out.append(None if best is None else best[2])
    return out


def load(path: str):
    """The planes of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path).planes


def reduce_dir(trace_dir: str) -> dict:
    """:func:`reduce` of the newest trace under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(list(load(max(paths, key=os.path.getmtime))))


def module_seconds(reduced: dict, substring: str) -> float:
    return sum(v[1] for k, v in reduced["modules"].items() if substring in k)


def op_stats(reduced: dict, substring: str):
    """(events, seconds) of the operations whose name contains ``substring``."""
    count = seconds = 0
    for k, v in reduced["ops"].items():
        if substring in k:
            count += v[0]
            seconds += v[1]
    return count, seconds

