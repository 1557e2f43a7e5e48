"""Least time of the SVD++ training steps' required work (bench/
work_svdpp.py, counted by the driver from its own batches and sets N(u)
at the window's pair ranks) at the chip's peaks, over the epoch-scan
program's device time, in percent."""
from bench import trace, work
from bench.train_metrics import SCAN


def read(run):
    if run.trace is None or "svdpp" not in run.ctx:
        return None
    seconds = trace.module_seconds(run.trace, SCAN)
    if not seconds:
        return None
    ctx = run.ctx["svdpp"]
    return 100.0 * work.roofline_s(ctx["flops"], ctx["bytes"], run.peak) / seconds
