"""Least time of the scoring work the batch window requires (every served
user against the catalog at effective ranks, bench/work.py) at the chip's
peaks, over the pruned_topk kernel's device time, in percent."""
import numpy as np

from bench import trace, work


def read(run):
    if run.trace is None or "batch" not in run.ctx:
        return None
    ctx = run.ctx["batch"]
    count, seconds = trace.op_stats(run.trace, trace.TOPK_KERNEL)
    if not count:
        return None
    launches = ctx["launches"]
    least = 0.0
    for part in np.array_split(ctx["user_ranks"], launches):
        least += work.roofline_s(
            work.score_flops(part, ctx["h_item"]),
            work.score_bytes(part, ctx["h_item"], ctx["topk"]),
            run.peak,
        )
    return 100.0 * least / seconds
