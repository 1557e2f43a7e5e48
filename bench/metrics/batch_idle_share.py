"""Share of the traced batch window in which no operation ran on the
device, in percent (1 - busy / window, busy averaged over the chips)."""


def read(run):
    if run.trace is None or "batch" not in run.ctx:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
