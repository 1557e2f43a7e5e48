"""Scoring FLOPs the batch window requires at effective ranks, per second
of the window, as a share of the chip's bf16 peak, in percent."""
from bench import work


def read(run):
    if run.trace is None or "batch" not in run.ctx:
        return None
    ctx = run.ctx["batch"]
    flops = work.score_flops(ctx["user_ranks"], ctx["h_item"])
    return 100.0 * flops / run.trace["window_s"] / run.peak["bf16_flops_per_s"]
