"""Top-k for the whole user table in a seeded order, ``users_per_call``
users per ``ServingEngine.topk`` call, wrapping round the table.  The
answers of a seeded sample of users are compared, each user's last answer
in the window."""
import math
import time

import numpy as np

from bench import checks, serving
from bench.harness import Outcome, free, span

check = serving.check
controls = serving.controls


def drive(run, cfg: dict, traffic: dict, seed: int, seconds: float):
    engine, drawn, s_traffic = serving.engine(cfg, seed)
    rng = np.random.default_rng(s_traffic)
    m, per_call, topk = cfg["num_users"], traffic["users_per_call"], traffic["topk"]
    order = rng.permutation(m).astype(np.int32)
    sample = np.sort(rng.choice(m, traffic["sample_users"], replace=False))
    kept_s, kept_i = {}, {}

    def call(pos):
        ids = order[np.arange(pos, pos + per_call) % m]
        with span("bench.topk_call"):
            s, i = engine.topk(ids, topk)
        for row in np.nonzero(np.isin(ids, sample))[0]:
            kept_s[int(ids[row])], kept_i[int(ids[row])] = s[row], i[row]

    call(0)   # warm-up: compiles the launch bucket and builds the layout
    kept_s.clear()
    kept_i.clear()
    pos = calls = 0
    with run.window():
        t0 = time.perf_counter()
        while calls == 0 or time.perf_counter() - t0 < seconds:
            call(pos)
            pos = (pos + per_call) % m
            calls += 1
    users = calls * per_call
    run.log(f"{calls} calls of {per_call} users, {users} users in {run.window_s:.3f} s; "
            f"{len(kept_s)} sampled users answered")
    run.reduce_trace()
    if run.trace_dir:
        served = order[np.arange(users) % m]
        run.ctx["batch"] = dict(
            serving.ctx(drawn, served, calls * math.ceil(per_call / engine.max_batch)),
            users=users, topk=topk)
    del engine
    free()
    users_cmp = np.array(sorted(kept_s), np.int32)
    readings = checks.topk_gaps(
        drawn, users_cmp,
        np.array([kept_s[u] for u in users_cmp]).reshape(-1, topk),
        np.array([kept_i[u] for u in users_cmp]).reshape(-1, topk),
        topk=topk,
    )
    return Outcome(attempted=users, failed=0,
                   end_to_end={"batch_users_per_s": users / run.window_s},
                   readings=readings)
