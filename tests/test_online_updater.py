"""Incremental pruned updates (`online/updater.py`): parity with the
training step, power-of-two chunking, cold-start growth, drift recalibration."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import mf
from repro.core.trainer import DPMFTrainer, TrainConfig
from repro.data import build_user_history, synthetic_ratings, train_test_split
from repro.launch.mesh import make_mesh
from repro.online import EventBatch, OnlineUpdater
from repro.optim.optimizers import RowOptimizer


def _batch(users, items, ratings):
    return EventBatch(
        user=np.asarray(users, np.int32),
        item=np.asarray(items, np.int32),
        rating=np.asarray(ratings, np.float32),
    )


def _params(m=30, n=40, k=8, variant="funk", seed=0):
    return mf.init_params(
        jax.random.PRNGKey(seed), m, n, k, variant=variant, global_mean=3.0
    )


# ---------------------------------------------------------------------------
# parity with mf.train_step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant", ["funk", "bias"])
@pytest.mark.parametrize("optimizer", ["sgd", "adagrad"])
def test_apply_matches_train_step(variant, optimizer):
    """One full-bucket micro-batch must be EXACTLY one train_step."""
    params = _params(variant=variant)
    t = 0.05
    opt = RowOptimizer(name=optimizer)
    upd = OnlineUpdater(params, None, t, t, optimizer=optimizer,
                        lr=0.1, lam=0.02, batch_size=8)
    rng = np.random.default_rng(0)
    users = rng.integers(0, 30, 8)
    items = rng.integers(0, 40, 8)
    ratings = rng.uniform(1, 5, 8)
    upd.apply(_batch(users, items, ratings))

    want_params, _, want_metrics = mf.train_step(
        params, mf.init_opt_state(params, opt),
        {"user": jnp.asarray(users, jnp.int32),
         "item": jnp.asarray(items, jnp.int32),
         "rating": jnp.asarray(ratings, jnp.float32)},
        jnp.float32(t), jnp.float32(t), jnp.float32(0.1),
        jnp.ones((8,), jnp.float32), opt=opt, lam=0.02,
    )
    np.testing.assert_array_equal(np.asarray(upd.params.p),
                                  np.asarray(want_params.p))
    np.testing.assert_array_equal(np.asarray(upd.params.q),
                                  np.asarray(want_params.q))
    if variant == "bias":
        np.testing.assert_array_equal(np.asarray(upd.params.user_bias),
                                      np.asarray(want_params.user_bias))


def test_chunk_sizes_binary_decomposition():
    """Chunk shapes are powers of two (bounded jit cache), cover every
    event exactly once, and never need a padding row."""
    assert OnlineUpdater._chunk_sizes(5, 8) == [4, 1]
    assert OnlineUpdater._chunk_sizes(8, 8) == [8]
    assert OnlineUpdater._chunk_sizes(21, 8) == [8, 8, 4, 1]
    assert OnlineUpdater._chunk_sizes(1, 256) == [1]
    for total in range(1, 40):
        sizes = OnlineUpdater._chunk_sizes(total, 8)
        assert sum(sizes) == total
        assert all(s & (s - 1) == 0 and s <= 8 for s in sizes)


@pytest.mark.parametrize("optimizer", ["adagrad", "adam"])
def test_partial_batch_chunking_is_exact(optimizer):
    """A 5-event batch splits into [4, 1] chunks — identical to running
    train_step on those chunks by hand, for EMA-state optimizers too (no
    padding rows exist, so no duplicate-index scatter hazards)."""
    params = _params()
    opt = RowOptimizer(name=optimizer)
    upd = OnlineUpdater(params, None, 0.04, 0.04, optimizer=optimizer,
                        lr=0.1, lam=0.02, batch_size=8)
    rng = np.random.default_rng(1)
    users, items = rng.integers(0, 30, 5), rng.integers(0, 40, 5)
    ratings = rng.uniform(1, 5, 5)
    metrics = upd.apply(_batch(users, items, ratings))

    want_params = params
    want_state = mf.init_opt_state(params, opt)
    want_err = 0.0
    for sl in (slice(0, 4), slice(4, 5)):
        want_params, want_state, m = mf.train_step(
            want_params, want_state,
            {"user": jnp.asarray(users[sl], jnp.int32),
             "item": jnp.asarray(items[sl], jnp.int32),
             "rating": jnp.asarray(ratings[sl], jnp.float32)},
            jnp.float32(0.04), jnp.float32(0.04), jnp.float32(0.1),
            jnp.ones((8,), jnp.float32), opt=opt, lam=0.02,
        )
        want_err += float(m["abs_err"]) * (sl.stop - sl.start)
    np.testing.assert_array_equal(np.asarray(upd.params.p),
                                  np.asarray(want_params.p))
    np.testing.assert_array_equal(np.asarray(upd.params.q),
                                  np.asarray(want_params.q))
    state_key = "acc" if optimizer == "adagrad" else "v"
    np.testing.assert_array_equal(
        np.asarray(upd.opt_state.q[state_key]),
        np.asarray(want_state.q[state_key]),
    )
    assert metrics["abs_err"] == pytest.approx(want_err / 5, rel=1e-6)


def test_train_step_zero_weight_rows_are_inert():
    """The core weighted step (the hook for importance weighting): rows with
    weight 0 contribute nothing to factors, adagrad state, or metrics."""
    params = _params()
    opt = RowOptimizer(name="adagrad")
    rng = np.random.default_rng(1)
    users, items = rng.integers(0, 30, 5), rng.integers(0, 40, 5)
    ratings = rng.uniform(1, 5, 5)
    pad_u = np.pad(users, (0, 3), mode="edge")
    pad_i = np.pad(items, (0, 3), mode="edge")
    pad_r = np.pad(ratings, (0, 3), mode="edge")
    weight = np.asarray([1, 1, 1, 1, 1, 0, 0, 0], np.float32)
    got_params, got_state, got_m = mf.train_step(
        params, mf.init_opt_state(params, opt),
        {"user": jnp.asarray(pad_u, jnp.int32),
         "item": jnp.asarray(pad_i, jnp.int32),
         "rating": jnp.asarray(pad_r, jnp.float32),
         "weight": jnp.asarray(weight)},
        jnp.float32(0.04), jnp.float32(0.04), jnp.float32(0.1),
        jnp.ones((8,), jnp.float32), opt=opt, lam=0.02,
    )
    want_params, want_state, want_m = mf.train_step(
        params, mf.init_opt_state(params, opt),
        {"user": jnp.asarray(users, jnp.int32),
         "item": jnp.asarray(items, jnp.int32),
         "rating": jnp.asarray(ratings, jnp.float32)},
        jnp.float32(0.04), jnp.float32(0.04), jnp.float32(0.1),
        jnp.ones((8,), jnp.float32), opt=opt, lam=0.02,
    )
    np.testing.assert_array_equal(np.asarray(got_params.p),
                                  np.asarray(want_params.p))
    np.testing.assert_array_equal(np.asarray(got_params.q),
                                  np.asarray(want_params.q))
    np.testing.assert_array_equal(np.asarray(got_state.q["acc"]),
                                  np.asarray(want_state.q["acc"]))
    assert float(got_m["abs_err"]) == pytest.approx(
        float(want_m["abs_err"]), rel=1e-6
    )
    assert float(got_m["work_fraction"]) == pytest.approx(
        float(want_m["work_fraction"]), rel=1e-6
    )


def test_svdpp_apply_appends_history_and_touches_implicit():
    ds = synthetic_ratings(20, 25, 300, seed=0)
    params = _params(20, 25, 8, variant="svdpp")
    hist, _ = build_user_history(ds, 4)
    upd = OnlineUpdater(params, None, 0.0, 0.0, user_history=hist,
                        batch_size=8)
    # user 3 rates a brand-new-to-them item
    before = upd.user_history[3].copy()
    new_item = int((set(range(25)) - set(before.tolist())).pop())
    upd.apply(_batch([3], [new_item], [4.0]))
    assert new_item in upd.user_history[3]
    snap = upd.snapshot()
    assert 3 in snap.touched_users
    assert new_item in snap.touched_items
    # every live history item of user 3 had its implicit row updated
    live = [i for i in upd.user_history[3] if i < 25]
    assert set(live) <= set(snap.touched_implicit_items.tolist())


def test_svdpp_apply_compiles_one_step_per_chunk_size():
    """The updater fixes each chunk size's history capacity when it is
    built, so applies whose histories fill compile no more step programs
    than there are chunk sizes (32 and 16 here)."""
    ds = synthetic_ratings(40, 60, 200, seed=1)
    hist, _ = build_user_history(ds, 16)
    upd = OnlineUpdater(_params(40, 60, 8, variant="svdpp"), None, 0.0, 0.0,
                        user_history=hist, batch_size=32)
    live_before = int(np.sum(upd.user_history < 60))
    rng = np.random.default_rng(2)
    compiled = mf.train_step._cache_size()
    for _ in range(6):
        upd.apply(_batch(rng.integers(0, 40, 48), rng.integers(0, 60, 48),
                         rng.uniform(1, 5, 48)))
    assert int(np.sum(upd.user_history < 60)) > live_before + 200
    assert mf.train_step._cache_size() - compiled <= 2


def test_svdpp_requires_history():
    params = _params(variant="svdpp")
    with pytest.raises(ValueError, match="user_history"):
        OnlineUpdater(params, None, 0.0, 0.0)


# ---------------------------------------------------------------------------
# pruning does less work
# ---------------------------------------------------------------------------


def test_pruned_updates_do_less_work():
    params = _params(60, 80, 16, seed=2)
    rng = np.random.default_rng(2)
    users, items = rng.integers(0, 60, 64), rng.integers(0, 80, 64)
    ratings = rng.uniform(1, 5, 64)
    dense = OnlineUpdater(params, None, 0.0, 0.0, batch_size=64)
    m_dense = dense.apply(_batch(users, items, ratings))
    pruned = OnlineUpdater(params, None, 0.08, 0.08, batch_size=64)
    m_pruned = pruned.apply(_batch(users, items, ratings))
    assert m_dense["work_fraction"] == pytest.approx(1.0)
    assert m_pruned["work_fraction"] < 1.0
    assert pruned.mean_work_fraction < 1.0


# ---------------------------------------------------------------------------
# cold start growth
# ---------------------------------------------------------------------------


def test_cold_start_grows_tables_preserving_old_rows():
    params = _params(10, 12, 8, variant="bias")
    upd = OnlineUpdater(params, None, 0.03, 0.03, batch_size=8, seed=5)
    old_p = np.asarray(params.p).copy()
    old_q = np.asarray(params.q).copy()
    # user 14 and item 20 don't exist yet
    upd.apply(_batch([14, 2], [20, 3], [4.0, 2.0]))
    assert upd.num_users == 15 and upd.num_items == 21
    # untouched old rows byte-identical
    untouched_u = [u for u in range(10) if u != 2]
    np.testing.assert_array_equal(np.asarray(upd.params.p)[untouched_u],
                                  old_p[untouched_u])
    untouched_i = [i for i in range(12) if i != 3]
    np.testing.assert_array_equal(np.asarray(upd.params.q)[untouched_i],
                                  old_q[untouched_i])
    # new rows are initialized (not zero) and optimizer state grew with them
    assert np.abs(np.asarray(upd.params.p)[10:]).sum() > 0
    assert upd.opt_state.p["acc"].shape == (15, 8)
    assert upd.opt_state.q["acc"].shape == (21, 8)
    assert upd.params.user_bias.shape == (15, 1)
    assert upd.params.item_bias.shape == (21, 1)
    snap = upd.snapshot()
    # growth stays a row delta (grown rows are all touched); the engine
    # notices the changed catalog geometry by itself, so nothing here needs
    # the full-rebuild hammer
    assert not snap.full_rebuild
    assert {10, 11, 12, 13, 14} <= set(snap.touched_users.tolist())
    assert set(range(12, 21)) <= set(snap.touched_items.tolist())


def test_cold_start_svdpp_remaps_history_sentinel():
    params = _params(8, 10, 8, variant="svdpp")
    # hand-built histories: user 0 has items {1, 2}, everyone else empty —
    # the padding sentinel is the CURRENT catalog size, 10
    hist = np.full((8, 4), 10, np.int32)
    hist[0, :2] = [1, 2]
    n_pad_before = int((hist == 10).sum())
    upd = OnlineUpdater(params, None, 0.0, 0.0, user_history=hist,
                        batch_size=8)
    upd.apply(_batch([0], [12], [3.0]))  # item table grows 10 -> 13
    assert upd.num_items == 13
    assert upd.params.implicit.shape == (14, 8)
    # padding row is still the LAST row and still zero
    np.testing.assert_array_equal(
        np.asarray(upd.params.implicit[13]), np.zeros(8, np.float32)
    )
    # old sentinel 10 remapped to 13 (minus the slot the event filled)
    assert int((upd.user_history == 10).sum()) == 0
    assert int((upd.user_history == 13).sum()) == n_pad_before - 1
    assert 12 in upd.user_history[0]


def test_new_user_is_servable_after_update():
    """Cold-started rows must produce finite, usable predictions."""
    params = _params(10, 12, 8)
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=8, lr=0.2)
    for _ in range(5):
        upd.apply(_batch([11, 11], [0, 5], [5.0, 1.0]))
    pred, _ = mf.predict_pairs(
        upd.params, jnp.asarray([11, 11]), jnp.asarray([0, 5])
    )
    assert np.all(np.isfinite(np.asarray(pred)))
    # repeated 5-star ratings on item 0 vs 1-star on item 5 must separate
    assert float(pred[0]) > float(pred[1])


# ---------------------------------------------------------------------------
# drift recalibration
# ---------------------------------------------------------------------------


def test_recalibrate_preserves_predictions_and_permutes_state():
    ds = synthetic_ratings(60, 80, 4000, seed=0)
    train_ds, test_ds = train_test_split(ds, 0.2, seed=0)
    cfg = TrainConfig(k=12, epochs=3, batch_size=512, pruning_rate=0.3)
    tr = DPMFTrainer(cfg, train_ds, test_ds)
    tr.run()
    upd = OnlineUpdater.from_trainer(tr, batch_size=64)
    u = jnp.arange(20, dtype=jnp.int32)
    i = jnp.arange(20, dtype=jnp.int32)
    before, _ = mf.predict_pairs(upd.params, u, i)  # unpruned predictions
    acc_before = np.asarray(upd.opt_state.q["acc"]).copy()

    report = upd.maybe_recalibrate(force=True)
    assert report is not None and "perm" in report
    after, _ = mf.predict_pairs(upd.params, u, i)
    # the latent permutation preserves every inner product exactly
    np.testing.assert_allclose(np.asarray(before), np.asarray(after),
                               rtol=1e-6, atol=1e-6)
    # optimizer accumulators followed the same permutation
    np.testing.assert_array_equal(
        np.asarray(upd.opt_state.q["acc"]), acc_before[:, report["perm"]]
    )
    snap = upd.snapshot()
    assert snap.full_rebuild


def test_recalibrate_noop_within_budget_and_without_pruning():
    params = _params()
    upd = OnlineUpdater(params, None, 0.0, 0.0, pruning_rate=0.0)
    assert upd.drift() == 0.0
    assert upd.maybe_recalibrate() is None
    # with pruning: thresholds just solved from the current matrices drift ~0
    t_p, t_q = __import__(
        "repro.core.threshold", fromlist=["thresholds_from_matrices"]
    ).thresholds_from_matrices(params.p, params.q, 0.3)
    upd2 = OnlineUpdater(params, None, t_p, t_q, pruning_rate=0.3,
                         drift_budget=0.25)
    assert upd2.drift() < 0.05
    assert upd2.maybe_recalibrate() is None


# ---------------------------------------------------------------------------
# snapshot bookkeeping
# ---------------------------------------------------------------------------


def test_snapshot_resets_touched_sets():
    params = _params()
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=8)
    upd.apply(_batch([1, 2], [3, 4], [3.0, 4.0]))
    snap = upd.snapshot()
    assert set(snap.touched_users.tolist()) == {1, 2}
    assert set(snap.touched_items.tolist()) == {3, 4}
    assert snap.events_seen == 2
    empty = upd.snapshot()
    assert empty.touched_users.size == 0 and empty.touched_items.size == 0
    assert not empty.full_rebuild


def test_train_step_fractional_weight_scales_update_not_prediction():
    """Importance weighting: w=0.5 must halve the (SGD) update while the
    error is still computed against the FULL prediction, and the weighted
    metrics must not deflate."""
    params = _params()
    opt = RowOptimizer(name="sgd")
    u = jnp.asarray([3], jnp.int32)
    i = jnp.asarray([7], jnp.int32)
    r = jnp.asarray([4.0], jnp.float32)
    dim_mask = jnp.ones((8,), jnp.float32)

    full_params, _, full_m = mf.train_step(
        params, mf.init_opt_state(params, opt),
        {"user": u, "item": i, "rating": r},
        jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.1),
        dim_mask, opt=opt, lam=0.02,
    )
    half_params, _, half_m = mf.train_step(
        params, mf.init_opt_state(params, opt),
        {"user": u, "item": i, "rating": r,
         "weight": jnp.asarray([0.5], jnp.float32)},
        jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.1),
        dim_mask, opt=opt, lam=0.02,
    )
    full_delta = np.asarray(full_params.p - params.p)
    half_delta = np.asarray(half_params.p - params.p)
    np.testing.assert_allclose(half_delta, 0.5 * full_delta,
                               rtol=1e-6, atol=1e-7)
    # the error itself is against the full prediction -> same |err|, and the
    # weighted mean divides by sum(w)=0.5, not a clamped 1.0
    assert float(half_m["abs_err"]) == pytest.approx(
        float(full_m["abs_err"]), rel=1e-6
    )


def test_mesh_mode_updater_matches_single_device():
    """OnlineUpdater(mesh=...) — the distributed refresh path — matches the
    single-device updater through owner routing, a fractional time-decay
    weight column, and a cold-start growth event (rounded to mesh
    multiples)."""
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices (run under the 4-device CI mesh job)")
    from repro.online.stream import EventBatch

    mesh = make_mesh((2, 2), ("data", "model"))
    m, n, k = 16, 8, 12
    params = mf.init_params(jax.random.PRNGKey(0), m, n, k)
    rng = np.random.default_rng(3)
    # 32 events = one power-of-two chunk on the single-device path, so the
    # adagrad accumulator sees identical batch boundaries on both sides
    batches = [
        EventBatch(
            user=rng.integers(0, m, 32).astype(np.int32),
            item=rng.integers(0, n, 32).astype(np.int32),
            rating=rng.uniform(1, 5, 32).astype(np.float32),
            weight=rng.uniform(0.25, 1.0, 32).astype(np.float32),
        )
        for _ in range(3)
    ]
    # cold start past both tables: growth must round to the mesh multiples
    batches.append(EventBatch(
        user=np.asarray([m + 1], np.int32),
        item=np.asarray([n + 2], np.int32),
        rating=np.asarray([4.5], np.float32),
        weight=np.asarray([0.5], np.float32),
    ))

    ref_upd = OnlineUpdater(params, None, 0.05, 0.05, optimizer="adagrad",
                            lr=0.03, batch_size=64, seed=9)
    with jax.sharding.set_mesh(mesh):
        mesh_upd = OnlineUpdater(params, None, 0.05, 0.05,
                                 optimizer="adagrad", lr=0.03,
                                 batch_size=64, seed=9, mesh=mesh)
        for b in batches[:3]:
            ref_upd.apply(b)
            mesh_upd.apply(b)
        # exact parity over the routed, fractional-weight updates
        np.testing.assert_allclose(
            np.asarray(mesh_upd.params.p), np.asarray(ref_upd.params.p),
            atol=2e-7, rtol=0,
        )
        np.testing.assert_allclose(
            np.asarray(mesh_upd.params.q), np.asarray(ref_upd.params.q),
            atol=2e-7, rtol=0,
        )
        np.testing.assert_allclose(
            np.asarray(mesh_upd.opt_state.q["acc"]),
            np.asarray(ref_upd.opt_state.q["acc"]), atol=2e-7, rtol=0,
        )
        # cold start: growth rounds to mesh multiples; the rows that existed
        # before the growth event are untouched by it (fresh rows draw
        # different RNG streams on the two sides by design, so only the
        # pre-growth slabs compare)
        pre_p = np.asarray(mesh_upd.params.p)
        pre_q = np.asarray(mesh_upd.params.q)
        mesh_upd.apply(batches[3])
        assert mesh_upd.num_users % 2 == 0 and mesh_upd.num_users >= m + 2
        assert mesh_upd.num_items % 2 == 0 and mesh_upd.num_items >= n + 3
        np.testing.assert_allclose(
            np.asarray(mesh_upd.params.p[:m]), pre_p[:m], atol=2e-7, rtol=0
        )
        np.testing.assert_allclose(
            np.asarray(mesh_upd.params.q[:n]), pre_q[:n], atol=2e-7, rtol=0
        )
        # the grown row actually absorbed the event
        assert bool(np.all(np.isfinite(np.asarray(mesh_upd.params.p))))
        scores_after = mesh_upd.params.p[m + 1] @ mesh_upd.params.q[n + 2]
        assert np.isfinite(float(scores_after))
