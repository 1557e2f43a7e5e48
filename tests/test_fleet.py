"""Serving fleet: delta replication, version gating, routing, drain.

Covers the ISSUE-6 acceptance surface:

* lossless codec round trips bit-exact (scalars, empty, f32/i32, big rows);
* replication edge cases — duplicate delivery, out-of-order delivery,
  late join via ``kind=full`` + ``fold_deltas`` — all ending bitwise
  identical to a fresh single engine on the published params;
* rolling hot-swap across replicas under concurrent load: zero dropped
  requests, every replica converges to the published version;
* cache-affinity routing: repeat users stick to their replica, background
  priority traffic is never pinned, overloaded pins spill;
* graceful drain regression: ``engine.stop()`` under submit load strands
  no future and rejects (not resurrects) concurrent submits;
* a ``multiprocessing`` ProcessReplica smoke (marked slow).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import jax

from repro.core import mf
from repro.distributed.compression import (
    CompressedArray,
    compress_array,
    decompress_array,
)
from repro.online import (
    EventBatch,
    OnlineUpdater,
    SnapshotPublisher,
    fold_deltas,
)
from repro.serving import ServingEngine, load_mf_checkpoint
from repro.serving.fleet import (
    EngineDeltaSink,
    LocalReplica,
    ProcessReplica,
    Router,
    ServingFleet,
    VersionGate,
    apply_message,
    make_message,
    state_from_message,
    state_message,
)


def _params(m=40, n=300, k=8, variant="bias", seed=0):
    return mf.init_params(
        jax.random.PRNGKey(seed), m, n, k, variant=variant,
        **({"global_mean": 3.5} if variant != "funk" else {}),
    )


def _batch(rng, m, n, size=24):
    return EventBatch(
        user=rng.integers(0, m, size).astype(np.int32),
        item=rng.integers(0, n, size).astype(np.int32),
        rating=rng.uniform(1, 5, size).astype(np.float32),
    )


def _messages(n_publishes=3, m=40, n=300, seed=0, full_at=()):  # helper
    """Drive an updater through ``n_publishes`` snapshots and return the
    (messages, final updater) — the canonical wire sequence for gate tests."""
    rng = np.random.default_rng(seed)
    params = _params(m, n)
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=seed)
    msgs = []
    for v in range(1, n_publishes + 1):
        upd.apply(_batch(rng, m, n))
        msgs.append(make_message(
            upd.snapshot(), v, v - 1, full=(v in full_at), compress=True,
        ))
    return msgs, upd


def _assert_bitwise(engine_like, upd, topk=5):
    ref = ServingEngine(upd.params, upd.t_p, upd.t_q)
    users = np.arange(ref.num_users)
    s_ref, i_ref = ref.topk(users, topk)
    s, i = engine_like.topk(users, topk)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


# ---------------------------------------------------------------------------
# lossless codec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arr", [
    np.float32(3.5),
    np.zeros((), np.float64),
    np.empty((0, 8), np.float32),
    np.arange(7, dtype=np.int32),
    np.linspace(-2, 2, 4096, dtype=np.float32).reshape(64, 64),
    (np.random.default_rng(0).normal(size=(512, 24)) * 0.1).astype(np.float32),
], ids=["scalar32", "scalar64", "empty", "tiny-int", "grid", "factors"])
def test_codec_roundtrip_bit_exact(arr):
    c = compress_array(arr)
    back = decompress_array(c)
    assert back.shape == np.shape(arr)
    assert back.dtype == np.asarray(arr).dtype
    np.testing.assert_array_equal(back, np.asarray(arr))


def test_codec_compresses_factor_rows():
    rows = (np.random.default_rng(1).normal(size=(2048, 24)) * 0.1).astype(
        np.float32
    )
    c = compress_array(rows)
    assert c.codec == "shuffle-zlib"
    assert c.nbytes < c.raw_nbytes  # shuffle makes exponent bytes runs
    assert c.raw_nbytes == rows.nbytes


def test_codec_tiny_arrays_stored_raw():
    c = compress_array(np.arange(4, dtype=np.int8))
    assert c.codec == "raw" and c.nbytes == 4


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------


def test_message_matches_checkpoint_payload_and_applies_bitwise():
    msgs, upd = _messages(2)
    params0 = _params()
    state = (params0, 0.0, 0.0, None)
    for msg in msgs:
        state = apply_message(*state, msg)
    params, t_p, t_q, _ = state
    np.testing.assert_array_equal(np.asarray(params.p), np.asarray(upd.params.p))
    np.testing.assert_array_equal(np.asarray(params.q), np.asarray(upd.params.q))
    assert float(t_p) == float(upd.t_p) and float(t_q) == float(upd.t_q)


def test_message_wire_smaller_than_raw():
    msgs, _ = _messages(1, m=400, n=4000)
    assert msgs[0].wire_bytes < msgs[0].raw_bytes
    assert any(
        isinstance(v, CompressedArray) for v in msgs[0].tree.values()
    )


def test_state_message_roundtrip():
    params = _params(variant="svdpp")
    hist = np.random.default_rng(0).integers(0, 300, (40, 6)).astype(np.int32)
    msg = state_message(params, 0.1, 0.2, user_history=hist, version=7)
    got, t_p, t_q, history = state_from_message(msg)
    np.testing.assert_array_equal(np.asarray(got.p), np.asarray(params.p))
    np.testing.assert_array_equal(
        np.asarray(got.implicit), np.asarray(params.implicit)
    )
    np.testing.assert_array_equal(history, hist)
    assert msg.version == 7 and msg.kind == "full"


# ---------------------------------------------------------------------------
# version gating: duplicates, out-of-order, full fast-forward
# ---------------------------------------------------------------------------


def test_gate_applies_in_order_and_dedups():
    applied = []
    gate = VersionGate(lambda m: applied.append(m.version))
    msgs, _ = _messages(3)
    assert gate.offer(msgs[0]) == 1
    assert gate.offer(msgs[0]) == 1          # duplicate: acked, not applied
    assert gate.offer(msgs[1]) == 2
    assert gate.offer(msgs[2]) == 3
    assert applied == [1, 2, 3]
    assert gate.duplicates == 1 and gate.applied == 3


def test_gate_buffers_out_of_order_delivery():
    applied = []
    gate = VersionGate(lambda m: applied.append(m.version))
    msgs, _ = _messages(3)
    assert gate.offer(msgs[2]) == 0          # v3 before v1/v2: buffered
    assert gate.offer(msgs[1]) == 0          # v2 before v1: buffered
    assert applied == []
    assert gate.offer(msgs[0]) == 3          # v1 lands -> chain drains
    assert applied == [1, 2, 3]


def test_gate_full_fast_forwards_and_drops_stale_buffer():
    applied = []
    gate = VersionGate(lambda m: applied.append(m.version))
    msgs, _ = _messages(4, full_at=(3,))
    gate.offer(msgs[1])                      # v2 buffered (gap at v1)
    assert gate.offer(msgs[2]) == 3          # kind=full applies immediately
    assert applied == [3]
    assert gate.offer(msgs[0]) == 3          # v1 now stale: dropped
    assert gate.offer(msgs[1]) == 3          # v2 now stale: dropped
    assert gate.offer(msgs[3]) == 4
    assert applied == [3, 4]


def test_out_of_order_and_duplicates_converge_bitwise():
    msgs, upd = _messages(4, full_at=(2,))
    engine = ServingEngine(_params(), 0.0, 0.0)
    sink = EngineDeltaSink(engine)
    # adversarial delivery order with duplicates
    for msg in [msgs[1], msgs[0], msgs[0], msgs[3], msgs[2], msgs[1], msgs[3]]:
        sink.apply_update(msg)
    assert sink.version == 4
    _assert_bitwise(engine, upd)


# ---------------------------------------------------------------------------
# publisher as replication bus
# ---------------------------------------------------------------------------


def test_publisher_ships_to_subscribers_and_tracks_acks():
    rng = np.random.default_rng(2)
    params = _params()
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=2)
    engines = [ServingEngine(params, 0.0, 0.0) for _ in range(2)]
    pub = SnapshotPublisher(None, upd)
    for i, e in enumerate(engines):
        pub.subscribe(EngineDeltaSink(e, replica_id=f"r{i}"))
    for _ in range(3):
        upd.apply(_batch(rng, 40, 300))
        report = pub.publish()
    assert report.acked == {"r0": 3, "r1": 3}
    assert pub.lag() == 0 and pub.version == 3
    assert report.wire_bytes > 0
    for e in engines:
        _assert_bitwise(e, upd)


def test_publisher_heals_lagging_subscriber_with_full():
    rng = np.random.default_rng(3)
    params = _params()
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=3)
    pub = SnapshotPublisher(None, upd)
    engine = ServingEngine(params, 0.0, 0.0)
    sink = pub.subscribe(EngineDeltaSink(engine, replica_id="r0"))
    upd.apply(_batch(rng, 40, 300))
    pub.publish()
    # a second replica joins cold (version 0, missed v1): publisher sees the
    # stale ack and must ship kind=full next so its gate can apply it
    late_engine = ServingEngine(_params(seed=9), 0.0, 0.0)
    pub.subscribe(EngineDeltaSink(late_engine, replica_id="late"))
    upd.apply(_batch(rng, 40, 300))
    report = pub.publish()
    assert report.kind == "full"
    assert report.acked == {"r0": 2, "late": 2}
    _assert_bitwise(late_engine, upd)
    _assert_bitwise(engine, upd)
    del sink


def test_late_join_catches_up_from_checkpoints(tmp_path):
    """A replica bootstrapped from the delta-checkpoint chain via
    ``fold_deltas`` joins the live bus at the chain's last version and then
    follows deltas — bitwise identical to a bus-following replica."""
    rng = np.random.default_rng(4)
    params = _params()
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=4)
    engine = ServingEngine(params, 0.0, 0.0)
    pub = SnapshotPublisher(engine, upd, checkpoint_dir=str(tmp_path), keep=8)
    sink = pub.subscribe(EngineDeltaSink(
        ServingEngine(params, 0.0, 0.0), replica_id="r0"
    ))
    for _ in range(3):
        upd.apply(_batch(rng, 40, 300))
        pub.publish()
    pub.close()  # join async checkpoint writes

    # late joiner: fold the chain onto the same base the fleet launched from
    folded, f_tp, f_tq, _, last = fold_deltas(
        str(tmp_path), params, 0.0, 0.0
    )
    assert last == pub.version == 3
    late = LocalReplica("late", folded, f_tp, f_tq, base_version=last,
                        queue_kwargs={"linger_ms": 0.5})
    pub.subscribe(late)

    # both replicas now follow the live bus
    upd.apply(_batch(rng, 40, 300))
    report = pub.publish()
    assert report.kind == "delta"           # no heal needed: joined current
    assert report.acked["late"] == 4 and report.acked["r0"] == 4
    _assert_bitwise(late.engine, upd)
    _assert_bitwise(sink.engine, upd)
    late.close()


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


class _StubReplica:
    """Deterministic replica for routing tests: settable depth, counts."""

    def __init__(self, rid, depth=0):
        self.replica_id = rid
        self.version = 0
        self._depth = depth
        self.submitted = []

    def submit(self, user_id, topk=10, *, timeout=None, priority=0):
        self.submitted.append(user_id)
        fut = Future()
        fut.set_result((np.zeros(topk), np.arange(topk)))
        return fut

    def apply_update(self, msg):
        self.version = msg.version
        return self.version

    def depth(self):
        return self._depth

    def stats(self):
        return {"replica_id": self.replica_id, "version": self.version}

    def close(self):
        pass


def test_router_pins_repeat_users():
    reps = [_StubReplica("a"), _StubReplica("b")]
    router = Router(reps, overload_slack=4)
    first = router.pick(7)
    for _ in range(5):
        assert router.pick(7) == first
    assert router.affinity_hits == 5 and router.affinity_cold == 1


def test_router_background_priority_not_pinned():
    reps = [_StubReplica("a", depth=0), _StubReplica("b", depth=3)]
    router = Router(reps)
    assert router.pick(1, priority=1) == 0   # least depth
    assert router.affinity_cold == 0         # background never pins
    reps[0]._depth = 10
    assert router.pick(1, priority=1) == 1   # follows depth, no stickiness


def test_router_spills_overloaded_pin():
    reps = [_StubReplica("a", depth=0), _StubReplica("b", depth=0)]
    router = Router(reps, overload_slack=2)
    pin = router.pick(3)
    reps[pin]._depth = 100                   # pinned replica falls behind
    other = router.pick(3)
    assert other != pin and router.affinity_spills == 1
    reps[pin]._depth = 0                     # re-pinned to the new replica
    assert router.pick(3) == other


def test_router_random_policy_ignores_affinity():
    reps = [_StubReplica("a"), _StubReplica("b")]
    router = Router(reps, policy="random", seed=0)
    picks = {router.pick(5) for _ in range(64)}
    assert picks == {0, 1}
    assert router.affinity_hits == 0


def test_router_random_never_polls_depth():
    """ISSUE-7 bugfix: random routing must not pay a depth() poll per
    replica per request — for process replicas that is lock + dict work on
    the hot path for a signal the policy never reads."""

    class _NoDepth(_StubReplica):
        def depth(self):
            raise AssertionError("random policy polled depth()")

    router = Router([_NoDepth("a"), _NoDepth("b")], policy="random", seed=1)
    picks = {router.pick(u) for u in range(64)}
    assert picks == {0, 1}
    # the load-aware policies still read it, of course
    router_least = Router([_StubReplica("a"), _StubReplica("b", depth=5)],
                          policy="least")
    assert router_least.pick(0) == 0


def test_router_rolling_threshold_rollout_acks_every_replica():
    class _ThresholdStub(_StubReplica):
        def set_thresholds(self, t_p, t_q):
            self.thresholds = (t_p, t_q)
            return self.version

    reps = [_ThresholdStub("a"), _ThresholdStub("b")]
    router = Router(reps)
    acks = router.apply_thresholds(0.03, 0.04)
    assert acks == {"a": 0, "b": 0}
    assert all(r.thresholds == (0.03, 0.04) for r in reps)


def test_router_rolling_update_acks_every_replica():
    reps = [_StubReplica("a"), _StubReplica("b"), _StubReplica("c")]
    router = Router(reps)
    msgs, _ = _messages(1)
    acks = router.apply_update(msgs[0])
    assert acks == {"a": 1, "b": 1, "c": 1}
    assert router.version == 1


# ---------------------------------------------------------------------------
# fleet under load: rolling refresh, zero drops, convergence
# ---------------------------------------------------------------------------


def test_fleet_rolling_swap_under_load_zero_drops():
    rng = np.random.default_rng(5)
    params = _params()
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=5)
    fleet = ServingFleet(params, 0.0, 0.0, replicas=2, backend="local",
                         queue_kwargs={"linger_ms": 0.5})
    pub = SnapshotPublisher(None, upd)
    pub.subscribe(fleet.router)

    failures, done = [], []
    stop = threading.Event()

    def client(seed):
        crng = np.random.default_rng(seed)
        while not stop.is_set():
            try:
                fleet.submit(int(crng.integers(0, 40)), 5,
                             timeout=30.0).result(60)
                done.append(1)
            except Exception as exc:  # noqa: BLE001
                failures.append(repr(exc))

    threads = [threading.Thread(target=client, args=(100 + i,), daemon=True)
               for i in range(4)]
    for t in threads:
        t.start()
    for _ in range(3):                        # three rolling refreshes
        upd.apply(_batch(rng, 40, 300))
        pub.publish()
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    versions = [r.version for r in fleet.replicas]
    fleet.close()
    assert not failures, failures[:3]
    assert len(done) > 0
    assert versions == [3, 3] == [pub.version] * 2
    for r in fleet.replicas:
        _assert_bitwise(r.engine, upd)


def test_fleet_affinity_warms_caches():
    """Same hot-user traffic: the affinity router must land a higher
    hot-user cache hit rate than random routing (per-replica cache smaller
    than the hot set, SVD++ so the cache is live)."""
    m, n, k = 120, 600, 8
    params = _params(m, n, k, variant="svdpp")
    hist = np.random.default_rng(0).integers(0, n, (m, 4)).astype(np.int32)
    hot = np.random.default_rng(1).choice(m, 40, replace=False)
    rng = np.random.default_rng(2)
    users = np.where(rng.random(240) < 0.8,
                     hot[rng.integers(0, len(hot), 240)],
                     rng.integers(0, m, 240))
    rates = {}
    for policy in ("affinity", "random"):
        # per-replica capacity 24: the hot set split across 2 pinned
        # replicas (~20 each) fits, but random routing exposes each replica
        # to all 40 hot users and thrashes
        fleet = ServingFleet(
            params, 0.0, 0.0, replicas=2, backend="local",
            user_history=hist,
            engine_kwargs={"cache_size": 24},
            queue_kwargs={"linger_ms": 0.5},
            router_kwargs={"policy": policy, "seed": 3},
        )
        # serial traffic: queue depths stay ~0, so the routing decision
        # (not overload spill) is what's under test
        for u in users:
            fleet.submit(int(u), 5, timeout=60.0).result(120)
        stats = fleet.stats()
        hits = sum(r["cache_hits"] for r in stats["replicas"])
        misses = sum(r["cache_misses"] for r in stats["replicas"])
        fleet.close()
        rates[policy] = hits / max(hits + misses, 1)
    assert rates["affinity"] > rates["random"], rates


# ---------------------------------------------------------------------------
# graceful drain regression
# ---------------------------------------------------------------------------


def test_engine_stop_strands_no_future_under_load():
    """Regression: ``stop()`` under concurrent submit load used to let a
    racing ``submit`` auto-start a fresh queue nobody owned — its futures
    hung forever.  Now every accepted future resolves and in-drain submits
    are rejected with ``RuntimeError``."""
    engine = ServingEngine(_params(), 0.0, 0.0)
    engine.start(linger_ms=0.5, max_pending=512)
    futures, rejected = [], []
    stop_submitting = threading.Event()

    def submitter(seed):
        srng = np.random.default_rng(seed)
        while not stop_submitting.is_set():
            try:
                futures.append(engine.submit(int(srng.integers(0, 40)), 5,
                                             timeout=30.0))
            except RuntimeError:
                rejected.append(1)           # stopping: expected, not a drop
            except Exception:
                rejected.append(1)

    threads = [threading.Thread(target=submitter, args=(i,), daemon=True)
               for i in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.2)                          # build a backlog under load
    engine.stop()
    stop_submitting.set()
    for t in threads:
        t.join(timeout=60)
    deadline = time.monotonic() + 60
    pending = [f for f in futures if not f.done()]
    while pending and time.monotonic() < deadline:
        time.sleep(0.05)
        pending = [f for f in futures if not f.done()]
    assert not pending, f"{len(pending)} futures stranded by stop()"
    # and the engine is restartable afterwards
    scores, items = engine.submit(3, 5).result(60)
    assert len(items) == 5
    engine.stop()


def test_engine_stop_rejects_concurrent_submits():
    engine = ServingEngine(_params(), 0.0, 0.0)
    for _ in range(64):
        engine.submit(1, 5, timeout=30.0)
    results = []

    def stopper():
        engine.stop()

    t = threading.Thread(target=stopper)
    t.start()
    # submits racing the drain either land on the pre-stop queue or get a
    # clean rejection — never a zombie queue
    for _ in range(50):
        try:
            results.append(engine.submit(2, 5, timeout=30.0))
        except RuntimeError:
            pass
    t.join(60)
    for f in results:
        assert f.done() or f.result(60) is not None


# ---------------------------------------------------------------------------
# process replicas (slow: spawn + re-import)
# ---------------------------------------------------------------------------


def test_process_fleet_refuses_tpu_backend(monkeypatch):
    """On a TPU host the parent holds the chip, so a spawned replica could
    never reach it: the process backend must refuse at once (no child
    started, no wait for start_timeout) and say why."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    start = time.monotonic()
    with pytest.raises(RuntimeError, match="TPU host"):
        ServingFleet(_params(30, 200), 0.0, 0.0, replicas=2,
                     backend="process")
    assert time.monotonic() - start < 10.0


@pytest.mark.slow
def test_process_fleet_replicates_and_drains():
    rng = np.random.default_rng(6)
    params = _params(30, 200)
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=6)
    fleet = ServingFleet(params, 0.0, 0.0, replicas=2, backend="process",
                         queue_kwargs={"linger_ms": 1.0})
    try:
        pub = SnapshotPublisher(None, upd)
        pub.subscribe(fleet.router)
        futs = [fleet.submit(int(u), 5, timeout=60.0)
                for u in rng.integers(0, 30, 8)]
        upd.apply(_batch(rng, 30, 200))
        report = pub.publish()
        assert report.acked == {"r0": 1, "r1": 1}
        futs += [fleet.submit(int(u), 5, timeout=60.0)
                 for u in rng.integers(0, 30, 8)]
        for f in futs:
            scores, items = f.result(120)
            assert len(np.asarray(items)) == 5
        ref = ServingEngine(upd.params, upd.t_p, upd.t_q)
        s_ref, i_ref = ref.topk(np.arange(30), 5)
        for r in fleet.replicas:
            rows = [r.submit(u, 5, timeout=60.0) for u in range(30)]
            got_s = np.stack([np.asarray(f.result(120)[0]) for f in rows])
            got_i = np.stack([np.asarray(f.result(120)[1]) for f in rows])
            np.testing.assert_array_equal(got_s, np.asarray(s_ref))
            np.testing.assert_array_equal(got_i, np.asarray(i_ref))
            assert r.stats()["version"] == 1
    finally:
        fleet.close()


@pytest.mark.slow
def test_process_replica_late_join_from_checkpoints(tmp_path):
    """Spawn a ProcessReplica from checkpoint dirs: training base +
    online delta chain folded in the child (the fleet's cold-start path)."""
    from repro.checkpoint import checkpoint as ckpt_lib

    rng = np.random.default_rng(7)
    params = _params(30, 200)
    base_dir, online_dir = str(tmp_path / "train"), str(tmp_path / "online")
    ckpt_lib.save(base_dir, 1, {"params": params,
                                "t_p": np.float32(0.0),
                                "t_q": np.float32(0.0)})
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=7)
    pub = SnapshotPublisher(None, upd, checkpoint_dir=online_dir)
    for _ in range(2):
        upd.apply(_batch(rng, 30, 200))
        pub.publish()
    pub.close()

    base = load_mf_checkpoint(base_dir)
    rep = ProcessReplica("late", checkpoint=base_dir, online_dir=online_dir,
                         queue_kwargs={"linger_ms": 1.0})
    try:
        assert rep.version == 2
        ref = ServingEngine(upd.params, upd.t_p, upd.t_q)
        s_ref, i_ref = ref.topk(np.arange(30), 5)
        rows = [rep.submit(u, 5, timeout=60.0) for u in range(30)]
        got_s = np.stack([np.asarray(f.result(120)[0]) for f in rows])
        got_i = np.stack([np.asarray(f.result(120)[1]) for f in rows])
        np.testing.assert_array_equal(got_s, np.asarray(s_ref))
        np.testing.assert_array_equal(got_i, np.asarray(i_ref))
    finally:
        rep.close()
    del base
