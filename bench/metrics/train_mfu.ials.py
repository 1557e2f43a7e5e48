"""FLOPs the window's implicit-ALS epochs require at their ranks (the
Gramians and the Cholesky factorisations, bench/work_ials.py) per second
of the window, as a share of the chip's bf16 peak, in percent."""


def read(run):
    if run.trace is None or "ials" not in run.ctx:
        return None
    ctx = run.ctx["ials"]
    flops = ctx["epochs"] * (ctx["gram_flops"] + ctx["solve_flops"])
    return 100.0 * flops / run.trace["window_s"] / run.peak["bf16_flops_per_s"]
