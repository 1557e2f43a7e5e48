"""Least time of the implicit-ALS Gramians' required work (bench/
work_ials.py, counted by the driver from its own merged pairs at the
window's ranks) at the chip's peaks, over the device time of the Gramian
programs (``als_gram_ms.ials``), in percent."""
from bench import trace, work


def read(run):
    if run.trace is None or "ials" not in run.ctx:
        return None
    seconds = trace.module_seconds(run.trace, "als_gram")
    if not seconds:
        return None
    ctx = run.ctx["ials"]
    least = work.roofline_s(ctx["gram_flops"], ctx["gram_bytes"], run.peak)
    return 100.0 * ctx["epochs"] * least / seconds
