"""Async request pipeline (`serving/queue.py`): continuous batching must
change wall-clock only — never results, never ordering guarantees.

Determinism-critical tests drive a ``start=False`` queue with
:meth:`RequestQueue.drain_once` so batch composition is pinned; the thread
stress test runs the real scheduler thread under concurrent submitters.
"""
import threading
import time

import numpy as np
import jax
import pytest

from repro.core import mf
from repro.launch.mesh import make_mesh
from repro.serving import (
    QueueFullError,
    RequestQueue,
    RequestTimeout,
    ServingEngine,
)


@pytest.fixture(scope="module")
def engine():
    params = mf.init_params(
        jax.random.PRNGKey(0), 60, 500, 16, variant="bias", global_mean=3.0
    )
    return ServingEngine(
        params, 0.03, 0.03, use_kernel=False, block_n=128, max_batch=32
    )


# ---------------------------------------------------------------------------
# parity: queue-fed == synchronous path, byte for byte
# ---------------------------------------------------------------------------


def test_queue_batch_byte_identical_to_sync(engine):
    """One pinned batch (duplicates included) vs engine.topk on the same
    users: scores and indices must match bitwise, and duplicate user ids
    must fan out to identical rows."""
    users = [7, 3, 41, 3, 19, 7]
    q = RequestQueue(engine, start=False)
    futs = [q.submit(u, 6) for u in users]
    assert q.drain_once() == len(users)
    want_s, want_i = engine.topk(sorted(set(users)), 6)
    row = {u: r for r, u in enumerate(sorted(set(users)))}
    for u, fut in zip(users, futs):
        got_s, got_i = fut.result(0)
        assert np.array_equal(got_s, want_s[row[u]])
        assert np.array_equal(got_i, want_i[row[u]])
    q.close()


def test_queue_stress_threads_match_sequential(engine):
    """N threads x mixed-size (mixed-topk) requests through the live
    scheduler: every future completes and equals the sequential
    single-request result bitwise."""
    rng = np.random.default_rng(0)
    topks = (3, 7)
    expected = {
        k: engine.topk(np.arange(engine.num_users), k) for k in topks
    }
    q = RequestQueue(engine, linger_ms=1.0, max_pending=1024)
    failures = []

    def client(seed):
        crng = np.random.default_rng(seed)
        for _ in range(25):
            u = int(crng.integers(0, engine.num_users))
            k = int(crng.choice(topks))
            got_s, got_i = q.submit(u, k, timeout=120).result(timeout=120)
            want_s, want_i = expected[k]
            if not (
                np.array_equal(got_s, want_s[u])
                and np.array_equal(got_i, want_i[u])
            ):
                failures.append((u, k))

    threads = [
        threading.Thread(target=client, args=(seed,)) for seed in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "client thread hung"
    q.close()
    assert not failures, f"queue results diverged from sequential: {failures}"
    assert q.requests_served == 8 * 25
    assert q.batches_served <= q.requests_served  # coalescing happened at all
    del rng


# ---------------------------------------------------------------------------
# scheduling policy: deadline order, topk buckets
# ---------------------------------------------------------------------------


def test_deadline_order_within_bucket(engine):
    """Futures of one batch resolve in deadline order, not submit order."""
    q = RequestQueue(engine, start=False)
    order = []
    timeouts = [50.0, 10.0, 30.0, 20.0, 40.0]
    futs = []
    for tag, timeout in enumerate(timeouts):
        fut = q.submit(tag % engine.num_users, 5, timeout=timeout)
        fut.add_done_callback(lambda f, tag=tag: order.append(tag))
        futs.append(fut)
    assert q.drain_once() == len(timeouts)
    want = [tag for tag, _ in sorted(enumerate(timeouts), key=lambda p: p[1])]
    assert order == want
    q.close()


def test_earliest_deadline_picks_the_bucket(engine):
    """A batch is one topk bucket: the earliest-deadline request defines it
    and other buckets wait for the next launch."""
    q = RequestQueue(engine, start=False)
    late = [q.submit(u, 7, timeout=60.0) for u in (1, 2, 3)]
    urgent = q.submit(4, 3, timeout=5.0)
    assert q.drain_once() == 1  # only the topk=3 bucket
    assert urgent.done() and not any(f.done() for f in late)
    assert q.drain_once() == 3
    assert all(f.done() for f in late)
    q.close()


def test_mixed_topk_never_share_a_launch(engine):
    batches = []

    def spy(users, topk):
        batches.append((len(users), topk))
        return engine.topk(users, topk)

    q = RequestQueue(engine, score_fn=spy, start=False)
    for i in range(6):
        q.submit(i, 3 if i % 2 else 7)
    while q.drain_once():
        pass
    assert len(batches) == 2
    assert {(n, k) for n, k in batches} == {(3, 3), (3, 7)}
    assert q.batches_served == 2 and q.requests_served == 6
    q.close()


# ---------------------------------------------------------------------------
# timeouts, admission control, lifecycle
# ---------------------------------------------------------------------------


def test_expired_request_fails_not_scores(engine):
    q = RequestQueue(engine, start=False)
    doomed = q.submit(1, 5, timeout=1e-4)
    alive = q.submit(2, 5, timeout=60.0)
    time.sleep(0.01)
    assert q.drain_once() == 1  # only the live request reaches the engine
    with pytest.raises(RequestTimeout):
        doomed.result(0)
    assert alive.done() and q.expired == 1
    q.close()


def test_backpressure_rejects_and_counts(engine):
    q = RequestQueue(engine, max_pending=2, start=False)
    q.submit(1, 5)
    q.submit(2, 5)
    with pytest.raises(QueueFullError):
        q.submit(3, 5)
    assert q.rejected == 1
    assert q.drain_once() == 2  # the queue itself still drains fine
    q.close()


def test_backpressure_block_waits_for_space(engine):
    q = RequestQueue(engine, max_pending=1, start=False)
    first = q.submit(1, 5)
    drained = threading.Timer(0.05, q.drain_once)
    drained.start()
    fut = q.submit(2, 5, block=True, block_timeout=10.0)  # waits ~50ms
    drained.join()
    assert first.done() and not fut.done()
    assert q.drain_once() == 1 and fut.done()
    q.close()


def test_bad_request_fails_its_own_submit(engine):
    q = RequestQueue(engine, start=False)
    ok = q.submit(5, 5)
    with pytest.raises(ValueError):
        q.submit(engine.num_users + 7, 5)  # unknown user
    with pytest.raises(ValueError):
        q.submit(0, engine.n_items + 1)  # topk > n_items
    assert q.drain_once() == 1 and ok.done()
    q.close()


def test_close_drains_pending(engine):
    q = RequestQueue(engine)
    futs = [q.submit(u, 4) for u in range(10)]
    q.close()
    assert all(f.done() for f in futs)
    for f in futs:
        f.result(0)  # no exceptions
    with pytest.raises(RuntimeError):
        q.submit(0, 4)


def test_close_cancel_pending_fails_fast(engine):
    q = RequestQueue(engine, start=False)
    futs = [q.submit(u, 4) for u in range(3)]
    q.close(cancel_pending=True)
    for f in futs:
        with pytest.raises(RequestTimeout):
            f.result(0)


def test_cancelled_future_does_not_kill_scheduler(engine):
    """A caller cancelling its future (the natural follow-up to a client-side
    timeout) must not crash the scheduler thread: later requests still
    complete and the cancelled one is simply skipped."""
    q = RequestQueue(engine, start=False)
    doomed = q.submit(1, 5)
    assert doomed.cancel()
    survivor = q.submit(2, 5)
    assert q.drain_once() == 1  # the cancelled request never reaches scoring
    assert survivor.done() and doomed.cancelled()
    survivor.result(0)
    # the live scheduler keeps serving after a cancel too
    q.start()
    fut = q.submit(3, 5)
    fut.result(timeout=60)
    q.close()


def test_expired_requests_wake_blocked_submitters(engine):
    """Expiry frees queue space: a submitter blocked on backpressure must be
    woken when the scheduler drops expired entries, not wait forever."""
    q = RequestQueue(engine, max_pending=1, start=False)
    q.submit(1, 5, timeout=1e-4)  # will expire, freeing the only slot
    time.sleep(0.01)
    unblocked = []

    def blocked_submit():
        unblocked.append(q.submit(2, 5, block=True, block_timeout=30.0))

    t = threading.Thread(target=blocked_submit)
    t.start()
    time.sleep(0.05)           # let the submitter reach the wait
    assert q.drain_once() == 0  # only the expired request: nothing scored
    t.join(timeout=5)
    assert not t.is_alive(), "submitter still blocked after expiry freed space"
    assert q.drain_once() == 1 and unblocked[0].done()
    q.close()


# ---------------------------------------------------------------------------
# engine submit/poll frontend
# ---------------------------------------------------------------------------


def test_engine_concurrent_first_submit_single_queue():
    """Racing first submits must auto-start exactly one queue, never raise
    'already has a running request queue'."""
    params = mf.init_params(jax.random.PRNGKey(2), 20, 200, 8)
    eng = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64)
    barrier = threading.Barrier(8)
    errors, futs = [], []

    def first_submit(u):
        barrier.wait()
        try:
            futs.append(eng.submit(u, 4))
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=first_submit, args=(u,)) for u in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    for f in futs:
        f.result(timeout=60)
    eng.stop()


def test_engine_submit_autostarts_and_stops(engine):
    fut = engine.submit(3, 5)
    got_s, got_i = fut.result(timeout=60)
    want_s, want_i = engine.topk([3], 5)
    assert np.array_equal(got_s, want_s[0]) and np.array_equal(got_i, want_i[0])
    with pytest.raises(RuntimeError):
        engine.start()  # already running
    engine.stop()
    engine.stop()  # idempotent
    assert engine._queue is None


def test_engine_queue_sharded_scoring_parity(engine):
    """Queue-fed scoring through topk_sharded on a 1-way mesh must equal the
    local sync path bitwise (the 2-D layouts are covered on the 4-device CI
    mesh and the slow subprocess test in test_serving.py)."""
    mesh = make_mesh((1,), ("model",))
    engine.start(mesh=mesh)
    try:
        futs = [engine.submit(u, 6) for u in (0, 9, 33)]
        want_s, want_i = engine.topk([0, 9, 33], 6)
        for r, fut in enumerate(futs):
            got_s, got_i = fut.result(timeout=120)
            assert np.array_equal(got_s, want_s[r])
            assert np.array_equal(got_i, want_i[r])
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# priority classes
# ---------------------------------------------------------------------------


def test_priority_orders_within_deadline_bucket(engine):
    """No-deadline requests share one (infinite) bucket: lower priority
    values schedule first regardless of arrival order — with max_batch
    smaller than the backlog, the late high-priority request still makes
    the first launch and low-priority work waits."""
    batches = []

    def spy(users, topk):
        batches.append(list(users))
        return engine.topk(users, topk)

    q = RequestQueue(engine, score_fn=spy, start=False, max_batch=2)
    low = [q.submit(u, 5, priority=10) for u in (11, 12, 13)]
    urgent = q.submit(14, 5, priority=0)
    assert q.drain_once() == 2
    assert urgent.done()            # the high-priority request made batch 1
    assert 14 in batches[0]
    assert sum(f.done() for f in low) == 1  # only one low-prio slot remained
    while q.drain_once():
        pass
    assert all(f.done() for f in low)
    q.close()


def test_priority_never_starves_high_priority_under_flood(engine):
    """Continuous low-priority arrivals must not delay a high-priority
    request past the very next launch (the ROADMAP fairness item)."""
    q = RequestQueue(engine, start=False, max_batch=8)
    for u in range(16):
        q.submit(u % engine.num_users, 5, priority=10)
    for round_ in range(6):
        # a flood keeps arriving...
        for u in range(8):
            q.submit((round_ * 8 + u) % engine.num_users, 5, priority=10)
        # ...and one user-facing request lands
        vip = q.submit(round_ % engine.num_users, 5, priority=0)
        assert q.drain_once() > 0
        assert vip.done(), f"high-priority request starved in round {round_}"
    q.close()


def test_priority_does_not_override_earlier_deadline_bucket(engine):
    """A whole deadline bucket earlier beats any priority: urgency first,
    class second."""
    q = RequestQueue(engine, start=False, max_batch=1,
                     deadline_bucket_ms=50.0)
    slow_high = q.submit(1, 5, timeout=60.0, priority=0)
    fast_low = q.submit(2, 5, timeout=1.0, priority=10)
    assert q.drain_once() == 1
    assert fast_low.done() and not slow_high.done()
    assert q.drain_once() == 1
    assert slow_high.done()
    q.close()


def test_engine_submit_passes_priority(engine):
    fut_low = engine.submit(1, 5, priority=10)
    fut_high = engine.submit(2, 5, priority=0)
    for fut in (fut_low, fut_high):
        scores, items = fut.result(timeout=60)
        assert scores.shape == (5,) and items.shape == (5,)
    engine.stop()


# ---------------------------------------------------------------------------
# linger waits for SCHEDULABLE requests, not raw heap length (ISSUE-7 bugfix)
# ---------------------------------------------------------------------------


def test_linger_counts_only_schedulable_winning_bucket(engine):
    """The linger wait must fill the batch with requests that can actually
    join it.  Before the fix, raw heap length was compared to ``max_batch``,
    so other-topk-bucket (and expired) entries ended the linger early and
    the winning bucket launched underfilled."""
    calls = []
    real = engine.topk

    def spy(users, topk):
        calls.append((list(users), topk))
        return real(users, topk)

    q = RequestQueue(engine, score_fn=spy, max_batch=3, linger_ms=500.0)
    f0 = q.submit(10, 10, timeout=30.0, priority=0)
    # two other-bucket requests: with the bug, heap length hits max_batch=3
    # and the linger ends with the topk=10 bucket holding a single request
    other = [q.submit(u, 5, timeout=30.0, priority=5) for u in (11, 12)]
    time.sleep(0.1)
    late = [q.submit(u, 10, timeout=30.0, priority=0) for u in (13, 14)]
    for fut in [f0, *other, *late]:
        fut.result(timeout=60)
    q.close()
    first_topk10 = next(c for c in calls if c[1] == 10)
    assert sorted(first_topk10[0]) == [10, 13, 14], (
        "linger ended early: winning-bucket batch launched underfilled"
    )


def test_schedulable_locked_ignores_expired_and_other_buckets(engine):
    """Unit view of the counting rule the linger loop relies on."""
    q = RequestQueue(engine, start=False, max_batch=8)
    q.submit(1, 10, timeout=60.0)
    q.submit(2, 10, timeout=60.0)
    q.submit(3, 5, timeout=60.0)        # other topk bucket
    expired = q.submit(4, 10, timeout=1e-9)  # will be expired by now
    time.sleep(0.01)
    with q._cond:
        assert q._schedulable_locked() == 2
    # all-expired heap counts zero schedulable
    q2 = RequestQueue(engine, start=False)
    q2.submit(5, 10, timeout=1e-9)
    time.sleep(0.01)
    with q2._cond:
        assert q2._schedulable_locked() == 0
    q.close()
    q2.close()
    with pytest.raises(RequestTimeout):
        expired.result(0)
