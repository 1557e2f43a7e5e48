"""Cold-row eviction (`store/eviction.py`) + its remap threading through
the online updater, publisher, delta bus, and serving engine.

The contracts under test, in the order the module docstring states them:

* spill/revive is **bitwise**: an evicted row that comes back (because an
  event touched its external id) is exactly the row that left, factor +
  bias + optimizer state;
* compaction relocates but never alters surviving rows, and never touches
  the item table;
* the remap epoch is a barrier the whole delta fabric respects: a restart
  that folds the checkpoint chain across a compaction reconstructs the
  same external-id view (remap table included) as a live bus follower,
  down to identical top-k scores.
"""
import numpy as np
import jax
import pytest

from hypothesis_compat import given, settings, st
from repro.core import mf
from repro.online import EventBatch, OnlineUpdater
from repro.online.publisher import SnapshotPublisher, fold_deltas
from repro.serving import ServingEngine
from repro.serving.fleet import EngineDeltaSink
from repro.store import EvictionConfig, IdRemap, UserEvictor


def _params(m=24, n=40, k=6, seed=0, variant="bias"):
    return mf.init_params(
        jax.random.PRNGKey(seed), m, n, k, variant=variant, global_mean=3.0
    )


def _updater(m=24, n=40, *, variant="bias", seed=0, **kw):
    return OnlineUpdater(
        _params(m, n, seed=seed, variant=variant), None, 0.0, 0.0,
        batch_size=8, seed=seed, **kw,
    )


def _evictor(tmp_path, max_users, target=None):
    return UserEvictor(EvictionConfig(
        max_users=max_users, spill_dir=str(tmp_path / "spill"),
        target_users=target,
    ))


def _batch(rng, ext_max, size=8):
    return EventBatch(
        user=rng.integers(0, ext_max, size).astype(np.int32),
        item=rng.integers(0, 40, size).astype(np.int32),
        rating=rng.uniform(1, 5, size).astype(np.float32),
    )


def _live_rows(upd):
    """{ext_id: (p_row, bias_row)} for every currently-resident ext id."""
    remap = upd.evictor.remap
    p = np.asarray(upd.params.p)
    b = np.asarray(upd.params.user_bias)
    out = {}
    for ext in range(remap.num_external):
        phys = int(remap.ext_to_phys[ext])
        if phys >= 0:
            out[ext] = (p[phys].copy(), b[phys].copy())
    return out


# ---------------------------------------------------------------------------
# IdRemap basics
# ---------------------------------------------------------------------------

def test_idremap_lookup_unknown_and_spilled():
    remap = IdRemap(ext_to_phys=np.array([0, -1, 1], np.int32), epoch=3)
    got = remap.lookup(np.array([0, 1, 2, 7, -2]))
    assert got.tolist() == [0, -1, 1, -1, -1]
    assert remap.num_external == 3
    frozen = remap.as_array()
    frozen[0] = 99
    assert remap.ext_to_phys[0] == 0, "as_array must copy"


def test_eviction_config_validates_target():
    with pytest.raises(ValueError, match="target_users"):
        UserEvictor(EvictionConfig(max_users=10, spill_dir="/tmp/x",
                                   target_users=11))


def test_bind_rejects_svdpp(tmp_path):
    from repro.data import build_user_history, synthetic_ratings

    ds = synthetic_ratings(12, 20, 256, seed=0)
    hist, _ = build_user_history(ds, max_hist=4)
    upd = OnlineUpdater(
        _params(12, 20, variant="svdpp"), None, 0.0, 0.0,
        user_history=hist, batch_size=8,
    )
    with pytest.raises(ValueError, match="SVD"):
        upd.attach_evictor(_evictor(tmp_path, 10))


# ---------------------------------------------------------------------------
# spill / revive / compaction invariants
# ---------------------------------------------------------------------------

def test_evict_bounds_table_and_preserves_survivors(tmp_path):
    rng = np.random.default_rng(0)
    upd = _updater(m=16)
    upd.attach_evictor(_evictor(tmp_path, max_users=24, target=18))
    for ext_max in (16, 24, 30):   # grow past the watermark
        upd.apply(_batch(rng, ext_max))
    q_before = np.asarray(upd.params.q).copy()
    before = _live_rows(upd)
    report = upd.evictor.maybe_evict()
    assert report is not None and report["remap_epoch"] == 1
    assert upd.num_users == 18 <= 24
    assert np.array_equal(np.asarray(upd.params.q), q_before), (
        "user eviction must not touch the item table")
    after = _live_rows(upd)
    for ext, (p_row, b_row) in after.items():
        assert np.array_equal(p_row, before[ext][0]), (
            f"survivor ext {ext} factor row changed under compaction")
        assert np.array_equal(b_row, before[ext][1])
    # external domain is grow-only: nobody was forgotten, only spilled
    spilled = set(upd.evictor.spilled_external_ids().tolist())
    assert spilled == set(before) - set(after)


def test_revive_is_bitwise_roundtrip(tmp_path):
    rng = np.random.default_rng(1)
    upd = _updater(m=12)
    upd.attach_evictor(_evictor(tmp_path, max_users=16, target=10))
    for ext_max in (12, 20):
        upd.apply(_batch(rng, ext_max, size=16))
    before = _live_rows(upd)
    opt_before = {
        key: np.asarray(upd.opt_state.p[key]).copy()
        for key in upd.opt_state.p
        if np.asarray(upd.opt_state.p[key]).ndim >= 1
        and np.asarray(upd.opt_state.p[key]).shape[0] == upd.num_users
    }
    phys_before = {
        ext: int(upd.evictor.remap.ext_to_phys[ext]) for ext in before
    }
    assert upd.evictor.maybe_evict() is not None
    spilled = upd.evictor.spilled_external_ids()
    assert spilled.size
    # scoring-only lookups leave spilled rows on disk...
    assert (upd.evictor.remap.lookup(spilled) == -1).all()
    # ...but an update revives them, bitwise
    phys = upd.evictor.resolve(spilled.astype(np.int32))
    p = np.asarray(upd.params.p)
    b = np.asarray(upd.params.user_bias)
    for ext, row in zip(spilled.tolist(), phys.tolist()):
        assert np.array_equal(p[row], before[ext][0])
        assert np.array_equal(b[row], before[ext][1])
        for key, table in opt_before.items():
            assert np.array_equal(
                np.asarray(upd.opt_state.p[key])[row],
                table[phys_before[ext]],
            ), f"optimizer state {key} not restored for ext {ext}"
    assert upd.evictor.revivals == spilled.size


@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    ops=st.lists(
        st.tuples(st.sampled_from(["apply", "grow", "evict"]),
                  st.integers(min_value=0, max_value=2**31 - 1)),
        min_size=3, max_size=12,
    ),
)
@settings(max_examples=20, deadline=None)
def test_evict_grow_evict_preserves_live_predictions(tmp_path_factory,
                                                     seed, ops):
    """Any evict→grow→evict interleaving: every external user's factor/bias
    rows survive relocation and spill/revive bitwise, so their predictions
    are unchanged by the memory manager."""
    tmp_path = tmp_path_factory.mktemp("evict_prop")
    rng = np.random.default_rng(seed)
    upd = _updater(m=10)
    upd.attach_evictor(_evictor(tmp_path, max_users=14, target=10))
    shadow = {}

    def snapshot_live():
        for ext, rows in _live_rows(upd).items():
            shadow[ext] = rows

    snapshot_live()
    ext_domain = 10
    for op, op_seed in ops:
        op_rng = np.random.default_rng(op_seed)
        if op == "grow":
            ext_domain += int(op_rng.integers(1, 6))
        if op in ("apply", "grow"):
            upd.apply(_batch(op_rng, ext_domain))
            snapshot_live()
        else:
            report = upd.evictor.maybe_evict()
            if report is not None:
                live = _live_rows(upd)
                for ext, (p_row, b_row) in live.items():
                    assert np.array_equal(p_row, shadow[ext][0]), (
                        f"ext {ext} factor row corrupted by eviction")
                    assert np.array_equal(b_row, shadow[ext][1])
    # final reconciliation: revive everything and demand bitwise parity
    # with the last value each row was seen holding
    all_ext = np.arange(upd.evictor.remap.num_external, dtype=np.int32)
    phys = upd.evictor.resolve(all_ext)
    p = np.asarray(upd.params.p)
    b = np.asarray(upd.params.user_bias)
    for ext, row in zip(all_ext.tolist(), phys.tolist()):
        assert np.array_equal(p[row], shadow[ext][0])
        assert np.array_equal(b[row], shadow[ext][1])


# ---------------------------------------------------------------------------
# remap threading: publisher -> bus -> engine, and the folded restart
# ---------------------------------------------------------------------------

def _drive(upd, pub, ev, rng, *, publishes=6):
    """Apply/publish loop that forces at least one compaction mid-chain."""
    bumps = 0
    for i in range(publishes):
        upd.apply(_batch(rng, 20 + 6 * i, size=16))
        if i >= 2 and ev.maybe_evict() is not None:
            bumps += 1
        pub.publish()
    assert bumps >= 1, "test setup never crossed a remap epoch"
    return bumps


def test_restart_across_remap_epoch_matches_live_replica(tmp_path):
    """`fold_deltas` over a chain containing a compaction reconstructs the
    remap table and serves every external user bitwise-identically to a
    replica that followed the bus live."""
    rng = np.random.default_rng(7)
    params = _params(m=20, n=40)
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=16, seed=7)
    ev = _evictor(tmp_path, max_users=30, target=24)
    upd.attach_evictor(ev)
    primary = ServingEngine(params, 0.0, 0.0)
    pub = SnapshotPublisher(primary, upd,
                            checkpoint_dir=str(tmp_path / "chain"), keep=32)
    follower = pub.subscribe(
        EngineDeltaSink(ServingEngine(params, 0.0, 0.0), replica_id="r0")
    )
    _drive(upd, pub, ev, rng)
    pub.close()
    live = follower.engine
    assert live.remap_epoch == ev.remap.epoch >= 1

    extras = {}
    folded, f_tp, f_tq, _, last = fold_deltas(
        str(tmp_path / "chain"), params, 0.0, 0.0, extras=extras,
    )
    assert last == pub.version
    assert extras["remap_epoch"] == ev.remap.epoch
    assert np.array_equal(extras["user_remap"], ev.remap.as_array())
    restarted = ServingEngine(
        folded, f_tp, f_tq,
        user_remap=extras["user_remap"], remap_epoch=extras["remap_epoch"],
    )
    users = np.arange(ev.remap.num_external, dtype=np.int32)
    s_live, i_live = live.topk(users, 5)
    s_cold, i_cold = restarted.topk(users, 5)
    np.testing.assert_array_equal(np.asarray(i_cold), np.asarray(i_live))
    np.testing.assert_array_equal(np.asarray(s_cold), np.asarray(s_live))
    # both views agree with the updater's own external-id geometry
    assert restarted.num_users == upd.num_users == live.num_users


def test_delta_after_remap_bump_keeps_following(tmp_path):
    """The publish *after* a compaction heals followers via kind=full; the
    ones after that go back to cheap deltas, remap intact."""
    rng = np.random.default_rng(3)
    params = _params(m=20, n=40)
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=16, seed=3)
    ev = _evictor(tmp_path, max_users=28, target=20)
    upd.attach_evictor(ev)
    pub = SnapshotPublisher(ServingEngine(params, 0.0, 0.0), upd)
    follower = pub.subscribe(
        EngineDeltaSink(ServingEngine(params, 0.0, 0.0), replica_id="r0")
    )
    upd.apply(_batch(rng, 20, size=16))
    pub.publish()                           # bootstrap
    upd.apply(_batch(rng, 40, size=16))     # past watermark
    assert ev.maybe_evict() is not None
    assert pub.publish().kind == "full"     # remap-epoch barrier
    # touch only still-resident users: no growth, no revival -> cheap delta
    live_ext = np.flatnonzero(ev.remap.ext_to_phys >= 0).astype(np.int32)
    upd.apply(EventBatch(
        user=rng.choice(live_ext, 16).astype(np.int32),
        item=rng.integers(0, 40, 16).astype(np.int32),
        rating=rng.uniform(1, 5, 16).astype(np.float32),
    ))
    report = pub.publish()
    assert report.kind == "delta"
    assert follower.engine.remap_epoch == ev.remap.epoch == 1
    users = np.arange(ev.remap.num_external, dtype=np.int32)
    ref = ServingEngine(
        upd.params, upd.t_p, upd.t_q,
        user_remap=ev.remap.as_array(), remap_epoch=ev.remap.epoch,
    )
    s_ref, i_ref = ref.topk(users, 5)
    s_got, i_got = follower.engine.topk(users, 5)
    np.testing.assert_array_equal(np.asarray(i_got), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(s_got), np.asarray(s_ref))
