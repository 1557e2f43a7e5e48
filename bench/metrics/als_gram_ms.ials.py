"""Device time of the implicit-ALS Gramians per epoch, in ms: the programs
``als_gram`` (each row block's chunk partials of (c - 1) y~ y~' and c y~,
summed per row) and ``als_gram_fixed`` (the fixed side's masked Gramian),
both half-steps, from the trace's "XLA Modules" line."""
from bench import trace

GRAM = "als_gram"   # matches both programs' names


def read(run):
    if run.trace is None or "ials" not in run.ctx:
        return None
    seconds = trace.module_seconds(run.trace, GRAM)
    if not seconds:
        return None
    return 1e3 * seconds / run.ctx["ials"]["epochs"]
