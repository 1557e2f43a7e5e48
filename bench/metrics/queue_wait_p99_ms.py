"""99th percentile of a request's wait from its due time to the start of
the scoring call that served it, in ms, from the benchmark's spans around
the queue's score function."""
import math


def read(run):
    waits = run.ctx.get("online", {}).get("queue_wait_ms")
    if not waits:
        return None
    s = sorted(waits)
    return float(s[max(math.ceil(0.99 * len(s)) - 1, 0)])
