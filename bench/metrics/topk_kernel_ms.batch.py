"""Device time of one pruned_topk kernel launch in the batch window, in ms."""
from bench import trace


def read(run):
    if run.trace is None or "batch" not in run.ctx:
        return None
    count, seconds = trace.op_stats(run.trace, trace.TOPK_KERNEL)
    if not count:
        return None
    return 1e3 * seconds / count
