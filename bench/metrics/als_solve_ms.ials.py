"""Device time of the implicit-ALS solves per epoch, in ms: the program
``als_solve`` (each row block's masked systems, Cholesky factors,
substitutions and row write), both half-steps, from the trace's "XLA
Modules" line."""
from bench import trace

SOLVE = "als_solve"


def read(run):
    if run.trace is None or "ials" not in run.ctx:
        return None
    seconds = trace.module_seconds(run.trace, SOLVE)
    if not seconds:
        return None
    return 1e3 * seconds / run.ctx["ials"]["epochs"]
