"""What the training cells' per-layer readers read, from the trace of the
window and the context ``training.finish`` leaves (``run.ctx["train"]``).
Each returns None where there is nothing to read."""
from bench import trace, work

SCAN = "train_epoch_scan"   # the epoch-scan program's name in the trace


def idle_share(run):
    """Share of the traced window in which no operation ran on the device,
    in percent (1 - busy / window, busy averaged over the chips)."""
    if run.trace is None or "train" not in run.ctx:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def step_us(run):
    """Device time of the epoch-scan program per training step, in us."""
    if run.trace is None or "train" not in run.ctx:
        return None
    seconds = trace.module_seconds(run.trace, SCAN)
    if not seconds:
        return None
    return 1e6 * seconds / run.ctx["train"]["steps"]


def step_roofline(run):
    """Least time of the training steps' required work at the chip's peaks,
    over the epoch-scan program's device time, in percent.  The work is
    counted at the window's pair ranks (bench/work.py), whatever the step
    executes."""
    if run.trace is None or "train" not in run.ctx:
        return None
    seconds = trace.module_seconds(run.trace, SCAN)
    if not seconds:
        return None
    least = sum(work.roofline_s(work.train_flops(n, r), work.train_bytes(n, r), run.peak)
                for n, r in run.ctx["train"]["pair_ranks"])
    return 100.0 * least / seconds


def mfu(run):
    """FLOPs the window's ratings require at their pair ranks, per second of
    the window, as a share of the chip's bf16 peak, in percent."""
    if run.trace is None or "train" not in run.ctx:
        return None
    flops = sum(work.train_flops(n, r) for n, r in run.ctx["train"]["pair_ranks"])
    return 100.0 * flops / run.trace["window_s"] / run.peak["bf16_flops_per_s"]
