"""Hot swap + publisher + the end-to-end freshness contract.

Covers the acceptance criteria of the online subsystem: per-version
determinism of the engine's atomic swap, touched-rows-only cache/layout
invalidation, delta-checkpoint durability, zero dropped requests across
swaps under concurrent load, and model freshness (recommendations move,
MAE stays within 5% of a full retrain, pruned updates do less work).
"""
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import mf
from repro.core.trainer import DPMFTrainer, TrainConfig
from repro.data import synthetic_ratings, train_test_split
from repro.data.ratings import RatingsDataset
from repro.online import (
    EventBatch,
    OnlineUpdater,
    ReplaySource,
    SnapshotPublisher,
    fold_deltas,
    iter_microbatches,
)
from repro.serving import ServingEngine, load_mf_checkpoint


def _params(m=40, n=600, k=16, variant="bias", seed=0):
    return mf.init_params(
        jax.random.PRNGKey(seed), m, n, k, variant=variant, global_mean=3.0
    )


def _perturb(params, touched_items, touched_users, seed=0, scale=0.05):
    rng = np.random.default_rng(seed)
    q = np.array(params.q)
    q[touched_items] += rng.normal(0, scale, (len(touched_items), q.shape[1])).astype(np.float32)
    p = np.array(params.p)
    p[touched_users] += rng.normal(0, scale, (len(touched_users), p.shape[1])).astype(np.float32)
    return params._replace(p=jnp.asarray(p), q=jnp.asarray(q))


# ---------------------------------------------------------------------------
# engine.swap semantics
# ---------------------------------------------------------------------------


def test_swap_incremental_matches_fresh_engine():
    """A touched-rows swap must serve exactly what a cold engine built from
    the new params serves — the patched tile/kernel layouts are not an
    approximation."""
    params = _params()
    engine = ServingEngine(params, 0.03, 0.03, use_kernel=False, block_n=128)
    users = np.arange(25, dtype=np.int32)
    engine.topk(users, 7)  # build the layout the swap will patch
    touched_i = np.asarray([0, 5, 128, 129, 599])
    touched_u = np.asarray([3, 9])
    new_params = _perturb(params, touched_i, touched_u)
    version = engine.swap(new_params, touched_users=touched_u,
                          touched_items=touched_i)
    assert version == 1 and engine.version == 1
    fresh = ServingEngine(new_params, 0.03, 0.03, use_kernel=False,
                          block_n=128)
    got_s, got_i = engine.topk(users, 7)
    want_s, want_i = fresh.topk(users, 7)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=0, atol=0)


def test_swap_kernel_layout_patched():
    params = _params()
    engine = ServingEngine(params, 0.03, 0.03, use_kernel=True,
                           interpret=True, max_batch=16)
    users = np.arange(9, dtype=np.int32)
    engine.topk(users, 5)
    touched_i = np.asarray([1, 2, 300])
    new_params = _perturb(params, touched_i, np.asarray([0]))
    engine.swap(new_params, touched_users=[0], touched_items=touched_i)
    fresh = ServingEngine(new_params, 0.03, 0.03, use_kernel=True,
                          interpret=True, max_batch=16)
    got_s, got_i = engine.topk(users, 5)
    want_s, want_i = fresh.topk(users, 5)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=0, atol=0)


def test_swap_threshold_change_forces_consistent_rebuild():
    """A swap that changes t_q cannot patch (every mask may change): it must
    rebuild and still match a fresh engine."""
    params = _params()
    engine = ServingEngine(params, 0.03, 0.03, use_kernel=False, block_n=128)
    engine.topk([0, 1], 5)
    engine.swap(params, 0.03, 0.06, touched_users=[], touched_items=[])
    fresh = ServingEngine(params, 0.03, 0.06, use_kernel=False, block_n=128)
    users = np.arange(20, dtype=np.int32)
    got_s, got_i = engine.topk(users, 6)
    want_s, want_i = fresh.topk(users, 6)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=0, atol=0)


def test_swap_growth_and_shrink_rejected():
    params = _params(m=10, n=50, k=8)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=32)
    engine.topk([0], 5)
    grown = mf.init_params(jax.random.PRNGKey(1), 14, 60, 8, variant="bias",
                           global_mean=3.0)
    engine.swap(grown, touched_users=None, touched_items=None)
    assert engine.num_users == 14 and engine.n_items == 60
    s, i = engine.topk([13], 5)  # the new user is servable
    assert s.shape == (1, 5)
    with pytest.raises(ValueError, match="shrink"):
        engine.swap(params)


def test_swap_versions_are_deterministic_per_batch():
    """Results must come from exactly one version: a batch scored before the
    swap equals version-0 output, after equals version-1, and nothing in
    between ever mixes rows."""
    params = _params()
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=128)
    users = np.arange(16, dtype=np.int32)
    v0_s, v0_i = engine.topk(users, 6)
    new_params = _perturb(params, np.arange(600), np.arange(40), scale=0.2)
    engine.swap(new_params, touched_users=None, touched_items=None)
    v1_s, v1_i = engine.topk(users, 6)
    fresh0 = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=128)
    fresh1 = ServingEngine(new_params, 0.0, 0.0, use_kernel=False,
                           block_n=128)
    assert np.array_equal(v0_i, fresh0.topk(users, 6)[1])
    assert np.array_equal(v1_i, fresh1.topk(users, 6)[1])
    assert not np.array_equal(v0_i, v1_i)  # the swap actually changed output


def test_swap_touched_only_lru_invalidation_svdpp():
    """Untouched users keep their cached vectors across a swap; touched
    users and users whose HISTORY contains a touched implicit row are
    evicted — and post-swap results still match a cold engine exactly."""
    m, n, k = 20, 60, 8
    params = _params(m, n, k, variant="svdpp")
    rng = np.random.default_rng(0)
    hist = rng.integers(0, n, (m, 4)).astype(np.int32)
    hist[7] = [50, 51, 52, 53]     # user 7's history hits touched item 50
    hist[5] = [10, 11, 12, 13]     # user 5's history avoids touched rows
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=32,
                           user_history=hist)
    engine.topk([3, 5, 7], 5)      # warm the cache
    assert len(engine.vector_cache) == 3

    touched_u, touched_i = [3], [50]
    new_params = _perturb(params, np.asarray(touched_i),
                          np.asarray(touched_u))
    y = np.array(params.implicit)
    y[50] += 0.3
    new_params = new_params._replace(implicit=jnp.asarray(y))
    engine.swap(new_params, touched_users=touched_u,
                touched_items=touched_i, touched_implicit_items=touched_i)

    # user 5 survived; users 3 (touched) and 7 (history hit) were evicted
    assert engine.vector_cache.get(5) is not None
    assert engine.vector_cache.get(3) is None
    assert engine.vector_cache.get(7) is None
    fresh = ServingEngine(new_params, 0.0, 0.0, use_kernel=False,
                          block_n=32, user_history=hist)
    got_s, got_i = engine.topk([3, 5, 7], 5)
    want_s, want_i = fresh.topk([3, 5, 7], 5)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# engine lifecycle (stop/start restart — regression for the swap-time drain)
# ---------------------------------------------------------------------------


def test_engine_stop_start_restart_cycle():
    params = _params(m=16, n=100, k=8, variant="funk")
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64)
    engine.stop()                      # stop before any start: no-op
    engine.start()
    s0 = engine.submit(1, 4).result(timeout=60)
    engine.stop()
    engine.stop()                      # idempotent
    engine.start()                     # restart after stop must work
    s1 = engine.submit(1, 4).result(timeout=60)
    assert np.array_equal(s0[1], s1[1])
    engine.stop()
    # submit after stop auto-starts a fresh queue
    s2 = engine.submit(1, 4).result(timeout=60)
    assert np.array_equal(s0[1], s2[1])
    engine.stop()


def test_engine_start_replaces_externally_closed_queue():
    params = _params(m=16, n=100, k=8, variant="funk")
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64)
    queue = engine.start()
    queue.close()                      # closed behind the engine's back
    queue2 = engine.start()            # must not raise "already running"
    assert queue2 is not queue
    engine.submit(0, 3).result(timeout=60)
    engine.stop()


# ---------------------------------------------------------------------------
# publisher + delta checkpoints
# ---------------------------------------------------------------------------


def test_publisher_delta_checkpoints_fold_to_live_state(tmp_path):
    ds = synthetic_ratings(60, 90, 3000, seed=0)
    train_ds, stream_ds = train_test_split(ds, 0.3, seed=0)
    cfg = TrainConfig(k=8, epochs=2, batch_size=512, pruning_rate=0.3,
                      variant="bias", checkpoint_dir=str(tmp_path / "base"))
    trainer = DPMFTrainer(cfg, train_ds, None)
    trainer.run()

    upd = OnlineUpdater.from_trainer(trainer, batch_size=64)
    engine = ServingEngine(trainer.params, trainer.t_p, trainer.t_q,
                           use_kernel=False, block_n=64)
    pub = SnapshotPublisher(engine, upd,
                            checkpoint_dir=str(tmp_path / "online"))
    for i, mb in enumerate(iter_microbatches(ReplaySource(stream_ds), 64)):
        upd.apply(mb)
        if i % 2 == 1:
            pub.publish()
    pub.publish()
    pub.close()

    base_params, t_p, t_q, _, _ = load_mf_checkpoint(str(tmp_path / "base"))
    folded, f_tp, f_tq, _, last = fold_deltas(
        str(tmp_path / "online"), base_params, t_p, t_q
    )
    np.testing.assert_array_equal(np.asarray(folded.p),
                                  np.asarray(upd.params.p))
    np.testing.assert_array_equal(np.asarray(folded.q),
                                  np.asarray(upd.params.q))
    np.testing.assert_array_equal(np.asarray(folded.user_bias),
                                  np.asarray(upd.params.user_bias))
    assert float(f_tq) == float(upd.t_q)
    assert last == engine.version


def test_publisher_full_checkpoint_after_recalibration(tmp_path):
    ds = synthetic_ratings(60, 90, 3000, seed=0)
    train_ds, stream_ds = train_test_split(ds, 0.3, seed=0)
    cfg = TrainConfig(k=8, epochs=2, batch_size=512, pruning_rate=0.3)
    trainer = DPMFTrainer(cfg, train_ds, None)
    trainer.run()
    upd = OnlineUpdater.from_trainer(trainer, batch_size=64)
    engine = ServingEngine(trainer.params, trainer.t_p, trainer.t_q,
                           use_kernel=False, block_n=64)
    pub = SnapshotPublisher(engine, upd,
                            checkpoint_dir=str(tmp_path / "online"))
    for mb in iter_microbatches(ReplaySource(stream_ds), 64):
        upd.apply(mb)
    assert upd.maybe_recalibrate(force=True) is not None
    report = pub.publish()
    pub.close()
    assert report.full_rebuild
    # a permuted latent axis cannot ride a row delta: the chain stays exact
    folded, _, _, _, _ = fold_deltas(
        str(tmp_path / "online"), trainer.params, trainer.t_p, trainer.t_q
    )
    np.testing.assert_array_equal(np.asarray(folded.p),
                                  np.asarray(upd.params.p))


# ---------------------------------------------------------------------------
# zero-downtime: swaps under concurrent load (acceptance criterion c)
# ---------------------------------------------------------------------------


def test_swaps_under_concurrent_load_drop_nothing():
    """>= 3 hot swaps while client threads hammer the async queue: every
    request completes, with the correct shape, from exactly one version."""
    params = _params(m=48, n=800, k=16)
    engine = ServingEngine(params, 0.03, 0.03, use_kernel=False, block_n=128)
    upd = OnlineUpdater(params, None, 0.03, 0.03, batch_size=64, lr=0.1)
    pub = SnapshotPublisher(engine, upd)
    for b in (1, 2, 4, 8):
        engine.topk(list(range(b)), 5)  # warm the buckets
    engine.start(linger_ms=1.0)

    stop = threading.Event()
    failures, completed = [], [0]
    lock = threading.Lock()

    def client(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            user = int(rng.integers(0, 48))
            try:
                s, i = engine.submit(user, 5, timeout=60).result(timeout=120)
                assert s.shape == (5,) and i.shape == (5,)
                with lock:
                    completed[0] += 1
            except Exception as exc:  # noqa: BLE001
                with lock:
                    failures.append(repr(exc))

    threads = [threading.Thread(target=client, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    rng = np.random.default_rng(9)
    try:
        for _ in range(4):  # > 3 consecutive swaps under load
            upd.apply(EventBatch(
                user=rng.integers(0, 48, 64).astype(np.int32),
                item=rng.integers(0, 800, 64).astype(np.int32),
                rating=rng.uniform(1, 5, 64).astype(np.float32),
            ))
            pub.publish()
            time.sleep(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
        engine.stop()
    assert engine.version == 4
    assert not failures, failures[:5]
    assert completed[0] > 0


# ---------------------------------------------------------------------------
# end-to-end freshness (acceptance criteria a, b, d)
# ---------------------------------------------------------------------------


def _concat(a: RatingsDataset, b: RatingsDataset) -> RatingsDataset:
    return RatingsDataset(
        user=np.concatenate([a.user, b.user]),
        item=np.concatenate([a.item, b.item]),
        rating=np.concatenate([a.rating, b.rating]),
        num_users=a.num_users, num_items=a.num_items,
        rating_min=a.rating_min, rating_max=a.rating_max,
    )


def test_online_freshness_end_to_end():
    """Train -> serve -> stream held-out events -> hot-swap:

    (a) recommendations for touched users move to reflect new interactions;
    (b) online MAE lands within 5% of a full retrain on the same events;
    (d) the pruned incremental updates did measurably less than dense work.
    """
    ds = synthetic_ratings(200, 300, 15000, seed=0)
    rest, test_ds = train_test_split(ds, 0.2, seed=0)
    train_ds, stream_ds = train_test_split(rest, 0.25, seed=1)
    cfg = TrainConfig(k=16, epochs=4, batch_size=1024, pruning_rate=0.3)

    retrain = DPMFTrainer(cfg, _concat(train_ds, stream_ds), test_ds)
    retrain.run()
    mae_retrain = retrain.evaluate()

    base = DPMFTrainer(cfg, train_ds, test_ds)
    base.run()
    engine = ServingEngine(base.params, base.t_p, base.t_q,
                           use_kernel=False, block_n=128)
    touched_users = np.unique(stream_ds.user)[:40]
    before_i = engine.topk(touched_users, 10)[1]

    upd = OnlineUpdater.from_trainer(base, batch_size=256, lr=0.02)
    pub = SnapshotPublisher(engine, upd)
    for ep in range(4):
        for mb in iter_microbatches(
            ReplaySource(stream_ds, shuffle=True, seed=ep), 256
        ):
            upd.apply(mb)
        pub.publish()

    # (d) pruned incremental updates skipped work
    assert upd.mean_work_fraction < 1.0

    # (a) the model moved for users with new interactions: their live top-10
    # changed for a clear majority (every set would be too strict — some
    # users' lists are genuinely stable)
    after_i = engine.topk(touched_users, 10)[1]
    changed = sum(
        not np.array_equal(before_i[r], after_i[r])
        for r in range(len(touched_users))
    )
    assert changed >= len(touched_users) // 2, (
        f"only {changed}/{len(touched_users)} touched users' top-10 moved"
    )
    # and the engine serves the updater's exact state (swap did its job)
    fresh = ServingEngine(upd.params, upd.t_p, upd.t_q,
                          use_kernel=False, block_n=128)
    np.testing.assert_array_equal(
        engine.topk(touched_users, 10)[1], fresh.topk(touched_users, 10)[1]
    )

    # (b) freshness quality: within 5% of the full retrain
    mae_online = upd.evaluate(test_ds)
    assert mae_online <= 1.05 * mae_retrain, (
        f"online MAE {mae_online:.4f} vs retrain {mae_retrain:.4f}"
    )


def test_online_svdpp_freshness_smoke():
    """SVD++ end to end: stream events extend histories, implicit rows
    update, the hot swap keeps serving exact (cold-engine-equal) results."""
    ds = synthetic_ratings(80, 120, 5000, seed=3)
    train_ds, stream_ds = train_test_split(ds, 0.25, seed=3)
    cfg = TrainConfig(k=8, epochs=2, batch_size=512, pruning_rate=0.3,
                      variant="svdpp", max_hist=8)
    trainer = DPMFTrainer(cfg, train_ds, None)
    trainer.run()
    upd = OnlineUpdater.from_trainer(trainer, batch_size=64)
    engine = ServingEngine(trainer.params, trainer.t_p, trainer.t_q,
                           use_kernel=False, block_n=64,
                           user_history=trainer.hist)
    pub = SnapshotPublisher(engine, upd)
    users = np.arange(30, dtype=np.int32)
    engine.topk(users, 6)  # warm cache + layout
    for mb in iter_microbatches(ReplaySource(stream_ds), 64, max_events=256):
        upd.apply(mb)
        pub.publish()
    fresh = ServingEngine(upd.params, upd.t_p, upd.t_q, use_kernel=False,
                          block_n=64, user_history=upd.user_history)
    got_s, got_i = engine.topk(users, 6)
    want_s, want_i = fresh.topk(users, 6)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=0, atol=0)


def test_delta_fold_across_cold_start_growth(tmp_path):
    """Growth stays a row delta: folding the chain must grow the base tables
    and land exactly on the live state."""
    params = _params(m=10, n=40, k=8)
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=2)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=32)
    pub = SnapshotPublisher(engine, upd,
                            checkpoint_dir=str(tmp_path / "online"))
    rng = np.random.default_rng(4)
    upd.apply(EventBatch(user=rng.integers(0, 10, 16).astype(np.int32),
                         item=rng.integers(0, 40, 16).astype(np.int32),
                         rating=rng.uniform(1, 5, 16).astype(np.float32)))
    pub.publish()
    upd.apply(EventBatch(user=np.asarray([13], np.int32),     # grows users
                         item=np.asarray([45], np.int32),     # grows items
                         rating=np.asarray([5.0], np.float32)))
    report = pub.publish()
    pub.close()
    assert not report.full_rebuild  # growth rides a delta, not a full dump
    folded, _, _, _, last = fold_deltas(
        str(tmp_path / "online"), params, 0.0, 0.0
    )
    assert folded.p.shape == (14, 8) and folded.q.shape == (46, 8)
    np.testing.assert_array_equal(np.asarray(folded.p),
                                  np.asarray(upd.params.p))
    np.testing.assert_array_equal(np.asarray(folded.q),
                                  np.asarray(upd.params.q))
    assert last == engine.version


def test_delta_chain_gc_anchor_and_break_detection(tmp_path):
    """Keep-N retention deletes old deltas; the publisher's periodic full
    anchors keep the surviving window replayable, and a chain with a
    missing predecessor raises instead of silently reconstructing stale
    state."""
    params = _params(m=12, n=30, k=8)
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=16, seed=0)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=32)
    keep = 4
    pub = SnapshotPublisher(engine, upd, keep=keep,
                            checkpoint_dir=str(tmp_path / "online"))
    rng = np.random.default_rng(1)
    for _ in range(10):  # > keep publishes: early deltas are GC'd
        upd.apply(EventBatch(
            user=rng.integers(0, 12, 16).astype(np.int32),
            item=rng.integers(0, 30, 16).astype(np.int32),
            rating=rng.uniform(1, 5, 16).astype(np.float32),
        ))
        pub.publish()
        pub.close()  # join each save so retention is deterministic
    from repro.checkpoint import checkpoint as ckpt_lib
    steps = ckpt_lib.all_steps(str(tmp_path / "online"))
    assert len(steps) == keep  # retention kicked in
    # the fold still reconstructs the exact live state (full anchor survives)
    folded, _, _, _, _ = fold_deltas(
        str(tmp_path / "online"), params, 0.0, 0.0
    )
    np.testing.assert_array_equal(np.asarray(folded.p),
                                  np.asarray(upd.params.p))
    np.testing.assert_array_equal(np.asarray(folded.q),
                                  np.asarray(upd.params.q))
    # sabotage: delete the anchor so the surviving deltas have no base
    fulls = [s for s in steps
             if ckpt_lib.load_metadata(str(tmp_path / "online"), s)["kind"]
             == "full"]
    assert fulls, "publisher must have written a periodic full anchor"
    for s in fulls:
        ckpt_lib._remove_step(str(tmp_path / "online"), s)
    with pytest.raises(ValueError, match="chain broken"):
        fold_deltas(str(tmp_path / "online"), params, 0.0, 0.0)


def test_publisher_resume_continues_chain_with_full_anchor(tmp_path):
    """A restarted publisher (fresh engine at version 0) must NOT overwrite
    existing chain steps: step numbering resumes from the directory frontier
    and the first post-restart checkpoint is a full anchor, so fold_deltas
    reconstructs the post-restart state."""
    params = _params(m=12, n=30, k=8)
    rng = np.random.default_rng(3)

    def feed(upd, pub, rounds):
        for _ in range(rounds):
            upd.apply(EventBatch(
                user=rng.integers(0, 12, 16).astype(np.int32),
                item=rng.integers(0, 30, 16).astype(np.int32),
                rating=rng.uniform(1, 5, 16).astype(np.float32),
            ))
            pub.publish()
        pub.close()

    # run 1: three deltas at steps 1..3
    upd1 = OnlineUpdater(params, None, 0.0, 0.0, batch_size=16, seed=0)
    eng1 = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=32)
    pub1 = SnapshotPublisher(eng1, upd1,
                             checkpoint_dir=str(tmp_path / "online"))
    feed(upd1, pub1, 3)

    # restart: resume from the folded state, engine version resets to 0
    folded, f_tp, f_tq, _, last = fold_deltas(
        str(tmp_path / "online"), params, 0.0, 0.0
    )
    assert last == 3
    upd2 = OnlineUpdater(folded, None, f_tp, f_tq, batch_size=16, seed=1)
    eng2 = ServingEngine(folded, f_tp, f_tq, use_kernel=False, block_n=32)
    pub2 = SnapshotPublisher(eng2, upd2,
                             checkpoint_dir=str(tmp_path / "online"))
    feed(upd2, pub2, 2)

    from repro.checkpoint import checkpoint as ckpt_lib
    steps = ckpt_lib.all_steps(str(tmp_path / "online"))
    assert steps == [1, 2, 3, 4, 5]  # nothing overwritten
    meta4 = __import__("json").load(open(
        tmp_path / "online" / "step_000000000004" / "metadata.json"))
    assert meta4["kind"] == "full"  # post-restart anchor
    refolded, _, _, _, last2 = fold_deltas(
        str(tmp_path / "online"), params, 0.0, 0.0
    )
    assert last2 == 5
    np.testing.assert_array_equal(np.asarray(refolded.p),
                                  np.asarray(upd2.params.p))
    np.testing.assert_array_equal(np.asarray(refolded.q),
                                  np.asarray(upd2.params.q))


def test_swap_accepts_one_shot_iterators():
    """The touched sets are walked several times inside swap (layout patch,
    user-const patch, LRU pruning): generator arguments must behave exactly
    like lists, not silently empty out after the first pass."""
    params = _params()
    engine = ServingEngine(params, 0.03, 0.03, use_kernel=False, block_n=128)
    users = np.arange(25, dtype=np.int32)
    engine.topk(users, 7)
    touched_i = [0, 5, 599]
    touched_u = [3, 9]
    new_params = _perturb(params, np.asarray(touched_i),
                          np.asarray(touched_u))
    engine.swap(new_params, touched_users=iter(touched_u),
                touched_items=iter(touched_i))
    fresh = ServingEngine(new_params, 0.03, 0.03, use_kernel=False,
                          block_n=128)
    got_s, got_i = engine.topk(users, 7)
    want_s, want_i = fresh.topk(users, 7)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=0, atol=0)
