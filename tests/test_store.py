"""Out-of-core store tests: Feistel permutation properties, columnar store
roundtrips, the prefetched slab loader's determinism/resume contract, the
store-mode trainer's mid-epoch checkpoint parity, and the device-resident
reshuffle of the in-memory ``PackedRatings`` path.

The bitwise assertions are deliberate: the resume story ("a killed run
replays the remaining slabs identically") only holds if the shuffled epoch
order is a pure function of ``(n, seed, epoch)`` and slab boundaries never
change what an example's batch assignment is.
"""
import os
import time

import numpy as np
import jax
import pytest

from hypothesis_compat import given, settings, st
from repro.core import trainer as trainer_lib
from repro.core.trainer import DPMFTrainer, TrainConfig
from repro.data import synthetic_ratings
from repro.data.loader import pack_ratings
from repro.store import (
    FeistelPermutation,
    RatingsStore,
    ShardedRatingsLoader,
    build_store,
)
from repro.store.ratings_store import permuted_indices


def _ds(n_ratings=2048, users=150, items=80, seed=0):
    return synthetic_ratings(users, items, n_ratings, seed=seed)


# ---------------------------------------------------------------------------
# Feistel permutation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 5, 64, 1000, 1024, 1025])
@pytest.mark.parametrize("seed,epoch", [(0, 0), (0, 7), (3, 1)])
def test_feistel_is_a_permutation(n, seed, epoch):
    perm = FeistelPermutation(n, seed, epoch)
    out = perm(np.arange(n))
    assert np.array_equal(np.sort(out), np.arange(n))


@given(
    n=st.integers(min_value=1, max_value=5000),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    epoch=st.integers(min_value=0, max_value=500),
)
@settings(max_examples=40, deadline=None)
def test_feistel_bijection_property(n, seed, epoch):
    out = FeistelPermutation(n, seed, epoch)(np.arange(n))
    assert out.min() >= 0 and out.max() < n
    assert np.unique(out).size == n, "collision — not a bijection"


def test_feistel_slice_parity():
    n, seed, epoch = 1337, 11, 4
    full = FeistelPermutation(n, seed, epoch)(np.arange(n))
    for start, count in [(0, 10), (100, 257), (n - 5, 5)]:
        got = permuted_indices(n, seed, epoch, start, count)
        assert np.array_equal(got, full[start:start + count])


def test_feistel_epochs_differ():
    n = 4096
    a = FeistelPermutation(n, 0, 0)(np.arange(n))
    b = FeistelPermutation(n, 0, 1)(np.arange(n))
    assert not np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Columnar store
# ---------------------------------------------------------------------------

def test_store_roundtrip_multi_shard(tmp_path):
    ds = _ds()
    directory = str(tmp_path / "store")
    # force several shards so gather crosses shard boundaries
    build_store(ds, directory, shard_rows=300)
    store = RatingsStore(directory)
    assert len(store) == len(ds)
    assert store.num_users == ds.num_users
    assert store.num_items == ds.num_items
    assert store.global_mean == pytest.approx(float(ds.global_mean))
    back = store.to_dataset()
    assert np.array_equal(back.user, ds.user)
    assert np.array_equal(back.item, ds.item)
    assert np.array_equal(back.rating, ds.rating)


def test_store_gather_arbitrary_order(tmp_path):
    ds = _ds()
    store = RatingsStore(build_store(ds, str(tmp_path / "s"), shard_rows=257))
    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(ds), 500)   # random order, duplicates likely
    user, item, rating = store.gather(idx)
    assert np.array_equal(user, ds.user[idx])
    assert np.array_equal(item, ds.item[idx])
    assert np.array_equal(rating, ds.rating[idx])
    with pytest.raises(IndexError):
        store.gather(np.array([len(ds)]))


def test_store_rejects_wrong_version(tmp_path):
    ds = _ds(256, 30, 20)
    directory = build_store(ds, str(tmp_path / "s"))
    import json

    path = os.path.join(directory, "index.json")
    with open(path) as f:
        index = json.load(f)
    index["version"] = 999
    with open(path, "w") as f:
        json.dump(index, f)
    with pytest.raises(ValueError, match="version"):
        RatingsStore(directory)


# ---------------------------------------------------------------------------
# Streaming slab loader
# ---------------------------------------------------------------------------

def _collect(loader, seed, epoch, **kw):
    slabs = list(loader.epoch_slabs(seed, epoch, **kw))
    return {
        key: np.concatenate([np.asarray(s.batches[key]) for s in slabs])
        for key in ("user", "item", "rating")
    }, slabs


def test_loader_epoch_determinism_and_coverage(tmp_path):
    ds = _ds()
    store = RatingsStore(build_store(ds, str(tmp_path / "s"), shard_rows=500))
    loader = ShardedRatingsLoader(store, 64, slab_steps=7, prefetch=2)
    a, slabs = _collect(loader, seed=3, epoch=5)
    b, _ = _collect(loader, seed=3, epoch=5)
    for key in a:
        assert np.array_equal(a[key], b[key]), "same (seed, epoch) diverged"
    assert sum(s.steps for s in slabs) == loader.num_steps
    assert [s.slab_idx for s in slabs] == list(range(loader.num_slabs))
    # the epoch covers num_steps*B distinct examples (shuffle is a bijection)
    perm = FeistelPermutation(len(store), 3, 5)
    idx = perm(np.arange(loader.num_steps * loader.batch_size))
    assert np.array_equal(a["rating"].reshape(-1), ds.rating[idx])
    c, _ = _collect(loader, seed=3, epoch=6)
    assert not np.array_equal(a["user"], c["user"]), "epochs share an order"


def test_loader_resume_matches_uninterrupted_tail(tmp_path):
    ds = _ds()
    store = RatingsStore(build_store(ds, str(tmp_path / "s")))
    loader = ShardedRatingsLoader(store, 64, slab_steps=5, prefetch=2)
    _, full = _collect(loader, seed=0, epoch=2)
    for start in (1, loader.num_slabs - 1, loader.num_slabs):
        tail = list(loader.epoch_slabs(0, 2, start_slab=start))
        assert len(tail) == loader.num_slabs - start
        for s_full, s_tail in zip(full[start:], tail):
            assert s_full.slab_idx == s_tail.slab_idx
            for key in s_full.batches:
                assert np.array_equal(
                    np.asarray(s_full.batches[key]),
                    np.asarray(s_tail.batches[key]),
                ), "resumed slab differs from the uninterrupted epoch"


def test_loader_no_shuffle_is_sequential(tmp_path):
    ds = _ds(640, 50, 30)
    store = RatingsStore(build_store(ds, str(tmp_path / "s")))
    loader = ShardedRatingsLoader(store, 32, slab_steps=4)
    got, _ = _collect(loader, seed=0, epoch=0, shuffle=False)
    n = loader.num_steps * loader.batch_size
    assert np.array_equal(got["user"].reshape(-1), ds.user[:n])


def test_loader_early_close_shuts_down_worker(tmp_path):
    ds = _ds()
    store = RatingsStore(build_store(ds, str(tmp_path / "s")))
    loader = ShardedRatingsLoader(store, 32, slab_steps=2, prefetch=2)
    before = threading_active_prefetchers()
    gen = loader.epoch_slabs(0, 0)
    next(gen)
    gen.close()   # abandon mid-epoch: must not hang or leak the thread
    assert threading_active_prefetchers() <= before + 0


def test_loader_runs_at_most_queue_depth_ahead(tmp_path):
    """The prefetch depth, not the data set, bounds what the loader holds:
    while the consumer holds slab s, the worker has built slabs up to
    s + prefetch + 1 (prefetch queued, one waiting to be put) and no
    further."""
    store = RatingsStore(build_store(_ds(), str(tmp_path / "s")))
    loader = ShardedRatingsLoader(store, 32, slab_steps=4, prefetch=2)
    assert loader.num_slabs > loader.prefetch + 2
    built = []
    host_slab = loader._host_slab

    def recording(perm, slab_idx):
        built.append(slab_idx)
        return host_slab(perm, slab_idx)

    loader._host_slab = recording
    for slab in loader.epoch_slabs(0, 0):
        ahead = min(slab.slab_idx + loader.prefetch + 1, loader.num_slabs - 1)
        deadline = time.monotonic() + 10.0
        while max(built) < ahead and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.02)   # room for a worker that ignored the bound
        assert max(built) == ahead, (slab.slab_idx, built)


def threading_active_prefetchers():
    import threading

    return sum(
        t.name == "ratings-prefetch" and t.is_alive()
        for t in threading.enumerate()
    )


def test_loader_validation():
    ds = _ds(100, 20, 10)
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        store = RatingsStore(build_store(ds, d))
        with pytest.raises(ValueError, match="batch_size"):
            ShardedRatingsLoader(store, 0)
        with pytest.raises(ValueError, match="nothing to stream"):
            # batch_size clamps to the dataset but 0 steps is an error only
            # when examples < 1 batch; craft that via huge batch over clamp
            ShardedRatingsLoader(
                RatingsStore(build_store(_ds(0, 5, 5), d + "/empty")), 8
            )
        loader = ShardedRatingsLoader(store, 16, slab_steps=2)
        with pytest.raises(ValueError, match="start_slab"):
            list(loader.epoch_slabs(0, 0, start_slab=loader.num_slabs + 1))


# ---------------------------------------------------------------------------
# Store-mode trainer: streamed epochs + mid-epoch checkpoint parity
# ---------------------------------------------------------------------------

def _store_cfg(store_dir, ckpt_dir=None):
    return TrainConfig(
        k=6, epochs=2, batch_size=32, lr=0.05, pruning_rate=0.5, seed=0,
        store_dir=store_dir, slab_steps=4, prefetch_slabs=2,
        checkpoint_dir=ckpt_dir, checkpoint_every_epochs=1,
        checkpoint_every_slabs=2,
    )


def _run_epochs(trainer, *, kill_after_scans=0):
    calls = {"n": 0}
    original = trainer_lib.mf.train_epoch_scan

    def counting(*args, **kwargs):
        calls["n"] += 1
        if kill_after_scans and calls["n"] > kill_after_scans:
            raise KeyboardInterrupt
        return original(*args, **kwargs)

    trainer_lib.mf.train_epoch_scan = counting
    try:
        while trainer.epoch < trainer.config.epochs:
            trainer.run_epoch()
    except KeyboardInterrupt:
        pass
    finally:
        trainer_lib.mf.train_epoch_scan = original
        if trainer._ckpt is not None:
            trainer._ckpt.wait()


def test_store_trainer_mid_epoch_resume_bitwise(tmp_path):
    ds = _ds(1024, 100, 60)
    store_dir = build_store(ds, str(tmp_path / "store"))

    baseline = DPMFTrainer(_store_cfg(store_dir))
    _run_epochs(baseline)
    num_slabs = baseline._loader.num_slabs
    assert num_slabs >= 4

    ckpt_dir = str(tmp_path / "ckpt")
    killed = DPMFTrainer(_store_cfg(store_dir, ckpt_dir))
    # epoch 0 runs num_slabs scans; die 3 scans into epoch 1, past the
    # slab-2 mid-epoch checkpoint
    _run_epochs(killed, kill_after_scans=num_slabs + 3)
    assert killed.epoch == 1, "kill should land mid-epoch-1"

    resumed = DPMFTrainer(_store_cfg(store_dir, ckpt_dir))
    assert resumed.maybe_restore()
    assert resumed.epoch == 1 and resumed._resume_slab == 2
    _run_epochs(resumed)

    assert np.array_equal(np.asarray(baseline.params.p),
                          np.asarray(resumed.params.p))
    assert np.array_equal(np.asarray(baseline.params.q),
                          np.asarray(resumed.params.q))
    for group in baseline.opt_state._fields:
        ga = getattr(baseline.opt_state, group)
        gb = getattr(resumed.opt_state, group)
        if isinstance(ga, dict):
            for key in ga:
                assert np.array_equal(np.asarray(ga[key]),
                                      np.asarray(gb[key])), (group, key)
    # the logged epoch metric is rebuilt from the checkpointed accumulators
    assert (baseline.history[-1].train_abs_err
            == resumed.history[-1].train_abs_err)


def test_store_trainer_matches_metadata(tmp_path):
    ds = _ds(512, 60, 40)
    store_dir = build_store(ds, str(tmp_path / "store"))
    trainer = DPMFTrainer(_store_cfg(store_dir))
    assert trainer.params.p.shape[0] == ds.num_users
    assert trainer.params.q.shape[0] == ds.num_items
    trainer.run_epoch()
    assert len(trainer.history) == 1
    assert np.isfinite(trainer.history[-1].train_abs_err)


# ---------------------------------------------------------------------------
# PackedRatings device-resident reshuffle (in-memory path)
# ---------------------------------------------------------------------------

def test_packed_reshuffle_determinism_and_distinct_epochs():
    ds = _ds(512, 60, 40)
    packed = pack_ratings(ds, 32)
    a = packed.epoch_batches(seed=1, epoch=3)
    b = packed.epoch_batches(seed=1, epoch=3)
    c = packed.epoch_batches(seed=1, epoch=4)
    for key in a:
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key]))
    assert not np.array_equal(np.asarray(a["user"]), np.asarray(c["user"]))


def test_packed_reshuffle_stays_on_device():
    ds = _ds(512, 60, 40)
    packed = pack_ratings(ds, 32)
    packed.epoch_batches(seed=0, epoch=0)   # warm: key upload + jit compile
    with jax.transfer_guard("disallow"):
        # later epochs must not round-trip the table (or the key) through
        # the host; the epoch scalar crosses via an explicit device_put
        out = packed.epoch_batches(seed=0, epoch=1)
    assert out["user"].shape == (packed.num_steps, 32)


# ---------------------------------------------------------------------------
# shard integrity (CRC-32 in index.json)
# ---------------------------------------------------------------------------

def test_corrupt_shard_quarantined(tmp_path):
    """A bit-flipped shard fails its index.json CRC on first open: the
    loader raises CorruptShardError and the file is quarantined."""
    from repro.store import CorruptShardError

    d = str(tmp_path / "store")
    build_store(_ds(), d, shard_rows=512)
    shard_path = os.path.join(d, "shard_00001.bin")
    blob = bytearray(open(shard_path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(shard_path, "wb").write(bytes(blob))

    store = RatingsStore(d)
    store._columns(0)                              # intact shard: fine
    with pytest.raises(CorruptShardError, match="crc32"):
        store._columns(1)
    assert os.path.exists(shard_path + ".corrupt")  # quarantined
    assert not os.path.exists(shard_path)


def test_corrupt_shard_caught_via_gather(tmp_path):
    from repro.store import CorruptShardError

    d = str(tmp_path / "store")
    build_store(_ds(), d, shard_rows=512)
    shard_path = os.path.join(d, "shard_00000.bin")
    blob = bytearray(open(shard_path, "rb").read())
    blob[0] ^= 0x01
    open(shard_path, "wb").write(bytes(blob))
    store = RatingsStore(d)
    with pytest.raises(CorruptShardError):
        store.gather(np.arange(16))


def test_shard_verification_is_once_and_optional(tmp_path):
    d = str(tmp_path / "store")
    build_store(_ds(), d, shard_rows=512)
    store = RatingsStore(d)
    store._columns(0)
    assert 0 in store._verified
    # opting out (trusted local disk): corrupt bytes flow through unchecked
    blob_path = os.path.join(d, "shard_00000.bin")
    unchecked = RatingsStore(d, verify_checksums=False)
    unchecked._columns(0)
    assert not unchecked._verified


def test_legacy_index_without_crc_loads(tmp_path):
    """Stores built before the checksum landed (no crc32 key) keep
    loading — verification is simply skipped for those shards."""
    import json

    d = str(tmp_path / "store")
    build_store(_ds(), d, shard_rows=512)
    index_path = os.path.join(d, "index.json")
    index = json.loads(open(index_path).read())
    for s in index["shards"]:
        s.pop("crc32")
    open(index_path, "w").write(json.dumps(index))
    store = RatingsStore(d)
    u, i, r = store.gather(np.arange(32))
    assert len(u) == 32 and not store._verified
