"""Open-loop single-user top-k requests through ``RequestQueue.submit`` at
a fixed rate.  Each request is timed from its due time to its result; one
that is refused or fails counts as +inf.  The answers of a seeded sample of
requests are compared, and a request that never got an answer is counted
as unanswered."""
import bisect
import time

import numpy as np

from bench import checks, serving
from bench.harness import Outcome, free, nearest_rank, span

check = serving.check
controls = serving.controls


def schedule(traffic: dict, num_users: int, seed: int, seconds: float):
    """Open-loop arrivals: ``rate_per_s * seconds`` requests whose gaps are
    the exponential law's quantiles and whose users are the truncated
    Zipf(``user_zipf``) law's quantiles over a seeded permutation of the
    ids, both in a seeded order -- every seed offers the same work."""
    rng = np.random.default_rng(seed)
    n = max(int(round(traffic["rate_per_s"] * seconds)), 1)
    quant = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-quant) / traffic["rate_per_s"]
    due = np.cumsum(rng.permutation(gaps)) - gaps.min()
    pmf = np.arange(1, num_users + 1, dtype=np.float64) ** -traffic["user_zipf"]
    cdf = np.cumsum(pmf) / pmf.sum()
    ranks = np.minimum(np.searchsorted(cdf, quant, side="right"), num_users - 1)
    users = rng.permutation(num_users)[rng.permutation(ranks)].astype(np.int32)
    return due, users


def drive(run, cfg: dict, traffic: dict, seed: int, seconds: float):
    from repro.serving.queue import RequestQueue

    engine, drawn, s_traffic = serving.engine(cfg, seed)
    topk = traffic["topk"]
    due, users = schedule(traffic, cfg["num_users"], s_traffic, seconds)
    n = len(due)
    sample = set(np.random.default_rng(s_traffic + 1).choice(
        n, min(traffic["sample_requests"], n), replace=False).tolist())
    launches = []   # (start, end, distinct users) of each scoring call

    def score(batch_users, k):
        t0 = time.perf_counter()
        with span("bench.launch"):
            out = engine.topk(batch_users, k)
        launches.append((t0, time.perf_counter(), len(batch_users)))
        return out

    # warm-up: every launch size -- a launch slices its results to its own
    # number of users on the device, which compiles once per size
    for b in range(1, engine.max_batch + 1):
        engine.topk(np.arange(b, dtype=np.int32), topk)
    queue = RequestQueue(engine, score_fn=score)
    for f in [queue.submit(int(u), topk) for u in users[:64]]:
        f.result()
    launches.clear()

    submitted = np.full(n, np.nan)
    done = np.full(n, np.inf)
    results = {}

    def finished(j, fut):
        t = time.perf_counter()
        if fut.exception() is None:
            done[j] = t
            if j in sample:
                results[j] = fut.result()

    refused = 0
    depth = []   # (due time, queue depth) every 256 requests
    with run.window():
        start = time.perf_counter() + 0.001
        due_abs = start + due
        for j in range(n):
            if j % 256 == 0:
                depth.append((due[j], queue.depth))
            wait = due_abs[j] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            submitted[j] = time.perf_counter()
            try:
                fut = queue.submit(int(users[j]), topk)
            except Exception:   # noqa: BLE001 - a refused request is a failed one
                refused += 1
                continue
            fut.add_done_callback(lambda f, j=j: finished(j, f))
        limit = time.perf_counter() + 60.0
        while np.isinf(done).sum() > refused and time.perf_counter() < limit:
            time.sleep(0.001)
    queue.close()
    lat_ms = (done - due_abs) * 1e3
    failed = int(np.isinf(lat_ms).sum())
    # the generator's lateness (due to submit) apart from the system's own
    # time (submit to result), which together make a request's latency
    late_ms = (submitted - due_abs) * 1e3
    own_ms = (done - submitted) * 1e3
    finite = np.isfinite(done)
    quarters = [np.mean([d for t, d in depth if q * due[-1] / 4 <= t < (q + 1) * due[-1] / 4]
                        or [0]) for q in range(4)]
    run.log(
        f"{n} requests due in {due[-1]:.3f} s, {queue.requests_served} served, "
        f"{failed} failed ({refused} refused); {len(launches)} launches; "
        f"latency p50 {nearest_rank(lat_ms, 0.5):.3f} ms, p99 "
        f"{nearest_rank(lat_ms, 0.99):.3f} ms; generator lateness p50 "
        f"{np.nanpercentile(late_ms, 50):.3f} ms, p99 {np.nanpercentile(late_ms, 99):.3f} ms, "
        f"max {np.nanmax(late_ms):.3f} ms; submit to result p50 "
        f"{nearest_rank(own_ms[finite], 0.5):.3f} ms, p99 "
        f"{nearest_rank(own_ms[finite], 0.99):.3f} ms; completed "
        f"{finite.sum() / (max(done[finite], default=np.nan) - start):.1f} req/s of "
        f"{n / due[-1]:.1f} offered; queue depth by quarter of the window "
        f"{[round(float(d), 1) for d in quarters]}"
    )
    run.reduce_trace()
    if run.trace_dir:
        ends = [e for _, e, _ in launches]
        waits = []
        for j in np.nonzero(finite)[0]:
            idx = bisect.bisect_right(ends, done[j]) - 1
            waits.append((launches[idx][0] - due_abs[j]) * 1e3)
        run.ctx["online"] = {
            "queue_wait_ms": waits,
            "launch_users": [u for _, _, u in launches],
        }
    del engine, queue
    free()
    ids = sorted(results)
    readings = checks.topk_gaps(
        drawn, users[ids],
        np.array([results[j][0] for j in ids]).reshape(-1, topk),
        np.array([results[j][1] for j in ids]).reshape(-1, topk),
        topk=topk,
    )
    readings["unanswered"] = float(failed)
    return Outcome(attempted=n, failed=failed,
                   end_to_end={"serve_p99_ms": nearest_rank(lat_ms, 0.99)},
                   readings=readings)
