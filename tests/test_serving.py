"""Serving engine: streaming top-k parity with the dense oracle, full
checkpoint round-trips (biases/implicit included), micro-batching, and the
catalog-sharded merge."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import mf
from repro.core.ranks import effective_ranks
from repro.core.trainer import DPMFTrainer, TrainConfig
from repro.data import synthetic_ratings, train_test_split
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh
from repro.serving import (
    LRUCache,
    MicroBatcher,
    ServingEngine,
    bucket_size,
    load_mf_checkpoint,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _random_factors(m, n, k, seed=0):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.normal(0, 0.1, (m, k)).astype(np.float32))
    q = jnp.asarray(rng.normal(0, 0.1, (n, k)).astype(np.float32))
    return p, q


# ---------------------------------------------------------------------------
# kernel / streaming top-k vs the dense argsort oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t", [0.0, 0.05])
@pytest.mark.parametrize("with_bias", [False, True])
def test_streaming_topk_matches_oracle(t, with_bias):
    p, q = _random_factors(40, 900, 24)
    bias = (
        jnp.asarray(np.random.default_rng(3).normal(0, 0.3, (900,)),
                    dtype=jnp.float32)
        if with_bias else None
    )
    r_u, r_i = effective_ranks(p, t), effective_ranks(q, t)
    want_s, want_i = ref.pruned_topk_ref(p, q, r_u, r_i, 11, item_bias=bias)
    got_s, got_i = ops.pruned_topk(
        p, q, t, t, 11, item_bias=bias, use_kernel=False, block_n=128
    )
    assert np.array_equal(np.asarray(want_i), np.asarray(got_i))
    np.testing.assert_allclose(
        np.asarray(want_s), np.asarray(got_s), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("t", [0.0, 0.05])
def test_pallas_topk_kernel_matches_oracle(t):
    p, q = _random_factors(40, 700, 24, seed=1)
    r_u, r_i = effective_ranks(p, t), effective_ranks(q, t)
    want_s, want_i = ref.pruned_topk_ref(p, q, r_u, r_i, 9)
    got_s, got_i = ops.pruned_topk(
        p, q, t, t, 9, use_kernel=True, interpret=True
    )
    assert np.array_equal(np.asarray(want_i), np.asarray(got_i))
    np.testing.assert_allclose(
        np.asarray(want_s), np.asarray(got_s), rtol=1e-5, atol=1e-5
    )


def test_topk_validates_k():
    p, q = _random_factors(4, 16, 8)
    with pytest.raises(ValueError):
        ops.pruned_topk(p, q, 0.0, 0.0, 17, use_kernel=False)
    with pytest.raises(ValueError):
        ops.pruned_topk(p, q, 0.0, 0.0, 0, use_kernel=False)


# ---------------------------------------------------------------------------
# engine vs predict_all_items (the retired serve path) across variants
# ---------------------------------------------------------------------------


def _dense_oracle(params, users, t_p, t_q, topk, hist=None):
    scores = mf.predict_all_items(
        params, users, t_p, t_q, use_kernel=False, hist=hist
    )
    idx = jnp.argsort(-scores, axis=1)[:, :topk].astype(jnp.int32)
    return np.asarray(jnp.take_along_axis(scores, idx, axis=1)), np.asarray(idx)


@pytest.mark.parametrize("variant", ["funk", "bias", "svdpp"])
def test_engine_matches_dense_serve_path(variant):
    m, n, k = 80, 1200, 16
    rng = np.random.default_rng(4)
    params = mf.init_params(
        jax.random.PRNGKey(0), m, n, k, variant=variant, global_mean=3.1
    )
    hist = (
        rng.integers(0, n, (m, 6)).astype(np.int32)
        if variant == "svdpp" else None
    )
    t = 0.04
    engine = ServingEngine(
        params, t, t, use_kernel=False, max_batch=32, block_n=256,
        user_history=hist,
    )
    users = rng.integers(0, m, 41).astype(np.int32)  # odd size: pad + chunk
    got_s, got_i = engine.topk(users, 7)
    want_s, want_i = _dense_oracle(
        params, jnp.asarray(users), t, t, 7,
        hist=None if hist is None else jnp.asarray(hist[users]),
    )
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)


def test_engine_kernel_path_matches_stream_path():
    params = mf.init_params(jax.random.PRNGKey(6), 40, 600, 16,
                            variant="bias", global_mean=3.0)
    stream = ServingEngine(params, 0.04, 0.04, use_kernel=False, block_n=128)
    kernel = ServingEngine(params, 0.04, 0.04, use_kernel=True,
                           interpret=True, max_batch=16)
    users = np.arange(13, dtype=np.int32)
    want_s, want_i = stream.topk(users, 6)
    got_s, got_i = kernel.topk(users, 6)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)


def test_engine_svdpp_missing_history_falls_back_to_p():
    """allow_missing_history serves SVD++ checkpoints from p alone (empty
    histories hit only the implicit table's zero padding row)."""
    params = mf.init_params(jax.random.PRNGKey(7), 20, 300, 8,
                            variant="svdpp", global_mean=3.0)
    with pytest.raises(ValueError):
        ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64,
                           allow_missing_history=True)
    users = np.arange(5, dtype=np.int32)
    got_s, got_i = engine.topk(users, 4)
    want_s, want_i = _dense_oracle(
        params, jnp.asarray(users), 0.0, 0.0, 4, hist=None
    )
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)


def test_engine_hot_user_cache_consistent():
    m, n, k = 30, 400, 8
    rng = np.random.default_rng(5)
    params = mf.init_params(jax.random.PRNGKey(1), m, n, k, variant="svdpp",
                            global_mean=3.0)
    hist = rng.integers(0, n, (m, 4)).astype(np.int32)
    engine = ServingEngine(params, 0.02, 0.02, use_kernel=False,
                           block_n=128, user_history=hist, cache_size=8)
    cold_s, cold_i = engine.topk([3, 5, 3], 5)
    assert engine.vector_cache.misses > 0
    warm_s, warm_i = engine.topk([3, 5, 3], 5)
    assert engine.vector_cache.hits > 0
    assert np.array_equal(cold_i, warm_i)
    np.testing.assert_allclose(cold_s, warm_s, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# checkpoint round-trip: biases and implicit factors survive serving restore
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_full_params(tmp_path):
    """BiasSVD checkpoints must serve with biases — the old loader dropped
    everything but p/q and silently served wrong scores."""
    ds = synthetic_ratings(60, 90, 2000, seed=0)
    train_ds, test_ds = train_test_split(ds, 0.2, seed=0)
    cfg = TrainConfig(
        k=8, epochs=2, batch_size=512, pruning_rate=0.3, variant="bias",
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    trainer = DPMFTrainer(cfg, train_ds, test_ds)
    trainer.run()

    params, t_p, t_q, perm, meta = load_mf_checkpoint(str(tmp_path / "ckpt"))
    assert params.user_bias is not None and params.item_bias is not None
    assert params.global_mean is not None
    np.testing.assert_array_equal(np.asarray(params.p),
                                  np.asarray(trainer.params.p))
    np.testing.assert_array_equal(np.asarray(params.user_bias),
                                  np.asarray(trainer.params.user_bias))
    assert float(t_p) == float(trainer.t_p)
    assert perm is not None

    engine = ServingEngine.from_checkpoint(
        str(tmp_path / "ckpt"), use_kernel=False, block_n=64
    )
    users = np.asarray([0, 7, 13], np.int32)
    got_s, got_i = engine.topk(users, 5)
    want_s, want_i = _dense_oracle(
        trainer.params, jnp.asarray(users), trainer.t_p, trainer.t_q, 5
    )
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)


def test_checkpoint_roundtrip_svdpp_implicit(tmp_path):
    from repro import checkpoint as ckpt_lib

    params = mf.init_params(jax.random.PRNGKey(2), 20, 30, 8, variant="svdpp",
                            global_mean=2.5)
    tree = {
        "params": params,
        "t_p": jnp.float32(0.03),
        "t_q": jnp.float32(0.04),
        "perm": jnp.arange(8, dtype=jnp.int32),
    }
    ckpt_lib.save(str(tmp_path / "ck"), 7, tree)
    loaded, t_p, t_q, perm, meta = load_mf_checkpoint(str(tmp_path / "ck"))
    assert loaded.implicit is not None
    np.testing.assert_array_equal(np.asarray(loaded.implicit),
                                  np.asarray(params.implicit))
    assert float(t_q) == pytest.approx(0.04)
    assert meta["step"] == 7


# ---------------------------------------------------------------------------
# micro-batching plumbing
# ---------------------------------------------------------------------------


def test_bucket_size_quantizes():
    assert [bucket_size(i, 8) for i in (1, 2, 3, 5, 8, 11)] == [1, 2, 4, 8, 8, 8]
    with pytest.raises(ValueError):
        bucket_size(0, 8)


def test_bucket_size_power_of_two_boundaries():
    """Exact powers of two map to themselves; one past a power doubles; the
    max_batch cap wins even when it is not itself a power of two."""
    for exp in range(0, 8):
        n = 1 << exp
        assert bucket_size(n, 256) == n
        assert bucket_size(n + 1, 256) == min(2 * n, 256)
    assert bucket_size(-1 + (1 << 8), 256) == 256
    # a non-power-of-two cap still bounds the bucket
    assert bucket_size(5, 6) == 6
    assert bucket_size(7, 6) == 6
    assert bucket_size(1, 1) == 1
    with pytest.raises(ValueError):
        bucket_size(-3, 8)


def test_lru_cache_evicts_in_order():
    cache = LRUCache(2)
    cache.put(1, "a")
    cache.put(2, "b")
    assert cache.get(1) == "a"      # refreshes 1
    cache.put(3, "c")               # evicts 2
    assert cache.get(2) is None
    assert cache.get(1) == "a" and cache.get(3) == "c"
    assert len(cache) == 2


def test_lru_cache_zero_capacity_disabled():
    """capacity<=0 means 'cache off': puts are dropped, gets miss, and the
    miss counter still ticks (the engine uses 0 for non-SVD++ variants)."""
    cache = LRUCache(0)
    cache.put(1, "a")
    assert cache.get(1) is None
    assert len(cache) == 0
    assert (cache.hits, cache.misses) == (0, 1)


def test_lru_cache_update_existing_key_refreshes():
    """Re-putting a key must update in place (len stays) AND refresh its
    recency, so it survives the next eviction."""
    cache = LRUCache(2)
    cache.put(1, "a")
    cache.put(2, "b")
    cache.put(1, "a2")              # update, not insert
    assert len(cache) == 2
    cache.put(3, "c")               # evicts 2 (1 was refreshed by the put)
    assert cache.get(2) is None
    assert cache.get(1) == "a2"


def test_lru_cache_hit_miss_counters_exact():
    cache = LRUCache(4)
    assert cache.get(9) is None
    cache.put(9, "x")
    assert cache.get(9) == "x"
    assert cache.get(9) == "x"
    assert cache.get(10) is None
    assert (cache.hits, cache.misses) == (2, 2)


def test_microbatcher_rejects_bad_ids_at_submit():
    """A bad user id must fail its own submit, not poison queued tickets."""
    params = mf.init_params(jax.random.PRNGKey(8), 16, 100, 8)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64)
    batcher = MicroBatcher(engine, topk=3)
    good = batcher.submit(5)
    with pytest.raises(ValueError):
        batcher.submit(999)
    results = batcher.drain()
    assert good in results and len(results) == 1


def test_microbatcher_validates_topk_at_construction():
    params = mf.init_params(jax.random.PRNGKey(8), 16, 100, 8)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64)
    with pytest.raises(ValueError, match="topk"):
        MicroBatcher(engine, topk=101)
    with pytest.raises(ValueError, match="topk"):
        MicroBatcher(engine, topk=0)
    MicroBatcher(engine, topk=100)  # topk == n_items is legal


def test_engine_validates_topk_bounds():
    """topk > n_items (or <= 0) must raise a clear request error up front,
    never a shape failure deep inside the lax.top_k trace — on every entry
    point."""
    params = mf.init_params(jax.random.PRNGKey(9), 12, 64, 8)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=32)
    for bad in (0, -1, 65):
        with pytest.raises(ValueError, match=r"topk must be in \[1, 64\]"):
            engine.topk([0], bad)
        with pytest.raises(ValueError, match=r"topk must be in \[1, 64\]"):
            engine.topk_sharded([0], bad, mesh=make_mesh((1,), ("model",)))
    s, i = engine.topk([0], 64)  # the boundary itself works
    assert s.shape == (1, 64) and i.shape == (1, 64)


def test_microbatcher_fans_out_duplicates():
    params = mf.init_params(jax.random.PRNGKey(3), 16, 200, 8)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64)
    batcher = MicroBatcher(engine, topk=4)
    t1, t2, t3 = batcher.submit(5), batcher.submit(9), batcher.submit(5)
    results = batcher.drain()
    assert set(results) == {t1, t2, t3}
    assert np.array_equal(results[t1][1], results[t3][1])
    _, want_i = engine.topk([9], 4)
    assert np.array_equal(results[t2][1], want_i[0])
    assert batcher.drain() == {}


# ---------------------------------------------------------------------------
# catalog-sharded serving
# ---------------------------------------------------------------------------


def test_sharded_topk_single_device_mesh():
    """The shard_map path on a trivial 1-way mesh must equal the local path
    (exercises specs + the cross-shard merge plumbing without subprocess)."""
    params = mf.init_params(jax.random.PRNGKey(4), 24, 500, 16,
                            variant="bias", global_mean=3.0)
    engine = ServingEngine(params, 0.03, 0.03, use_kernel=False, block_n=128)
    mesh = make_mesh((1,), ("model",))
    users = np.arange(10, dtype=np.int32)
    want_s, want_i = engine.topk(users, 6)
    got_s, got_i = engine.topk_sharded(users, 6, mesh=mesh)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)


def test_sharded_topk_2d_mesh_inprocess():
    """User-axis x item-axis (2-D) sharding parity.  Needs >= 4 local
    devices — skipped on the default 1-device run, exercised by the CI
    serving job (XLA_FLAGS=--xla_force_host_platform_device_count=4)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (run under the 4-device CI mesh job)")
    params = mf.init_params(jax.random.PRNGKey(11), 32, 900, 16,
                            variant="bias", global_mean=3.0)
    engine = ServingEngine(params, 0.03, 0.03, use_kernel=False, block_n=128)
    users = np.arange(13, dtype=np.int32)  # odd: exercises row-slab padding
    want_s, want_i = engine.topk(users, 6)
    for shape, names in [
        ((4,), ("model",)),            # 1-D: items only (the PR-1 layout)
        ((2, 2), ("data", "model")),   # 2-D: users x items
        ((4, 1), ("data", "model")),   # degenerate: users only
    ]:
        mesh = make_mesh(shape, names)
        got_s, got_i = engine.topk_sharded(users, 6, mesh=mesh)
        assert np.array_equal(want_i, got_i), (shape, names)
        np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_sharded_topk_multi_device():
    """Real 8-way sharding in a subprocess (device count must be set before
    jax initializes): the 1-D item-only layout, the 2-D user x item layout,
    and a single-user request whose batch must be padded to the user-slab
    multiple — all byte-identical to the local path."""
    code = """
        import numpy as np, jax
        from repro.core import mf
        from repro.launch.mesh import make_mesh
        from repro.serving import ServingEngine
        params = mf.init_params(jax.random.PRNGKey(0), 48, 2100, 24,
                                variant="bias", global_mean=3.0)
        engine = ServingEngine(params, 0.04, 0.04, use_kernel=False,
                               block_n=128)
        users = np.arange(17, dtype=np.int32)
        want_s, want_i = engine.topk(users, 9)
        for shape, names in [((8,), ("model",)),
                             ((2, 4), ("data", "model")),
                             ((4, 2), ("data", "model"))]:
            mesh = make_mesh(shape, names)
            got_s, got_i = engine.topk_sharded(users, 9, mesh=mesh)
            assert np.array_equal(want_i, got_i), (shape, names)
            np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)
        # bucket 1 < data extent: the engine must pad the user slab
        mesh = make_mesh((4, 2), ("data", "model"))
        one_s, one_i = engine.topk_sharded(users[3:4], 9, mesh=mesh)
        assert np.array_equal(one_i, want_i[3:4])
        np.testing.assert_allclose(one_s, want_s[3:4], rtol=1e-5, atol=1e-5)
        print("SHARDED_TOPK_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED_TOPK_OK" in proc.stdout


def test_sharded_topk_kernel_path_single_device_mesh():
    """Kernel-path (use_kernel=True) scoring under shard_map on a 1-way
    mesh: the per-shard Pallas pruned-topk kernel + cross-shard merge must
    equal the dense oracle exactly."""
    params = mf.init_params(jax.random.PRNGKey(5), 24, 500, 16,
                            variant="bias", global_mean=3.0)
    engine = ServingEngine(params, 0.03, 0.03, use_kernel=True,
                           interpret=True, max_batch=16)
    mesh = make_mesh((1,), ("model",))
    users = np.arange(9, dtype=np.int32)
    want_s, want_i = _dense_oracle(
        params, jnp.asarray(users), 0.03, 0.03, 6
    )
    got_s, got_i = engine.topk_sharded(users, 6, mesh=mesh)
    assert np.array_equal(want_i, got_i)
    np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)


def test_sharded_topk_kernel_path_4device_mesh():
    """Kernel-path scoring on the forced 4-device CPU mesh (the ROADMAP
    open item): item slabs shard over "model", each shard runs the fused
    pruned-score+top-k kernel in interpret mode, results pin to the dense
    oracle.  Skipped unless the CI serving-mesh job's device count is
    forced (XLA_FLAGS=--xla_force_host_platform_device_count=4)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (run under the 4-device CI mesh job)")
    params = mf.init_params(jax.random.PRNGKey(12), 32, 1100, 24,
                            variant="bias", global_mean=3.0)
    engine = ServingEngine(params, 0.04, 0.04, use_kernel=True,
                           interpret=True, max_batch=16)
    users = np.arange(13, dtype=np.int32)  # odd: row-slab padding
    want_s, want_i = _dense_oracle(
        params, jnp.asarray(users), 0.04, 0.04, 7
    )
    for shape, names in [
        ((4,), ("model",)),            # 1-D: item slabs only
        ((2, 2), ("data", "model")),   # 2-D: users x items
        ((4, 1), ("data", "model")),   # degenerate: users only
    ]:
        mesh = make_mesh(shape, names)
        got_s, got_i = engine.topk_sharded(users, 7, mesh=mesh)
        assert np.array_equal(want_i, got_i), (shape, names)
        np.testing.assert_allclose(want_s, got_s, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# latent-axis compaction (ISSUE-7: the FLOP-shedding behind SLO degradation)
# ---------------------------------------------------------------------------


def _grid_params(m, n, k, live_cols, seed=0):
    """Factor tables on the 1/8 grid (exact f32 dot products), with item
    factors zero beyond ``live_cols`` so effective ranks — and therefore the
    compacted latent width — are bounded by construction."""
    rng = np.random.default_rng(seed)
    p = (rng.integers(-16, 17, (m, k)) / 8.0).astype(np.float32)
    q = np.zeros((n, k), np.float32)
    live = (rng.integers(1, 17, (n, live_cols)) / 8.0).astype(np.float32)
    q[:, :live_cols] = live * rng.choice([-1.0, 1.0], (n, live_cols))
    return mf.MFParams(jnp.asarray(p), jnp.asarray(q), None, None, None, None)


def test_compact_latent_bitwise_equal_and_actually_truncates():
    """compact_latent=True must serve byte-identical results (grid inputs
    make exact equality the contract) while the streaming layout really is
    narrower than k."""
    k, live = 32, 12
    params = _grid_params(20, 500, k, live, seed=3)
    t = 0.05  # every |factor| >= 1/8 > t: ranks == live column count
    plain = ServingEngine(params, t, t, use_kernel=False, block_n=128)
    compact = ServingEngine(params, t, t, use_kernel=False, block_n=128,
                            compact_latent=True)
    users = np.arange(20)
    s0, i0 = plain.topk(users, 7)
    s1, i1 = compact.topk(users, 7)
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
    q_tiles = compact._snap.stream_layout()[0]
    assert q_tiles.shape[2] == 16   # round8(12) — truncated from 32
    assert plain._snap.stream_layout()[0].shape[2] == k


def test_compact_latent_disabled_at_rate_zero():
    """t == 0 means pruning disabled: compaction must not alter the layout
    and serving stays bitwise dense."""
    params = _grid_params(16, 300, 24, 24, seed=4)
    compact = ServingEngine(params, 0.0, 0.0, use_kernel=False, block_n=64,
                            compact_latent=True)
    assert compact._snap.stream_layout()[0].shape[2] == 24
    want_s, want_i = _dense_oracle(params, jnp.arange(16), 0.0, 0.0, 5)
    got_s, got_i = compact.topk(np.arange(16), 5)
    assert np.array_equal(want_i, np.asarray(got_i))
    assert np.array_equal(want_s, np.asarray(got_s))


def test_compact_swap_rebuilds_when_rank_outgrows_width():
    """An online update that grows a touched row's effective rank past the
    compacted width must force a full layout rebuild (a patch would silently
    truncate the new factors)."""
    k, live = 32, 12
    params = _grid_params(20, 500, k, live, seed=5)
    t = 0.05
    engine = ServingEngine(params, t, t, use_kernel=False, block_n=128,
                           compact_latent=True)
    engine.topk(np.arange(4), 5)  # force the (narrow) layout build
    assert engine._snap.stream_layout()[0].shape[2] == 16
    # touched item now uses ALL k latent columns
    q_new = np.asarray(params.q).copy()
    q_new[7] = (np.arange(k) % 8 + 1) / 8.0
    new_params = params._replace(q=jnp.asarray(q_new))
    engine.swap(new_params, t, t, touched_users=np.array([0]),
                touched_items=np.array([7]))
    # the rebuild widened the layout to cover the grown rank
    assert engine._snap.stream_layout()[0].shape[2] == k
    fresh = ServingEngine(new_params, t, t, use_kernel=False, block_n=128)
    s0, i0 = fresh.topk(np.arange(20), 7)
    s1, i1 = engine.topk(np.arange(20), 7)
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    assert np.array_equal(np.asarray(s0), np.asarray(s1))


def test_compact_swap_patches_when_rank_fits():
    """Touched rows whose ranks stay inside the compacted width keep the
    incremental patch path — and results stay bitwise right."""
    k, live = 32, 12
    params = _grid_params(20, 500, k, live, seed=6)
    t = 0.05
    engine = ServingEngine(params, t, t, use_kernel=False, block_n=128,
                           compact_latent=True)
    engine.topk(np.arange(4), 5)
    q_new = np.asarray(params.q).copy()
    q_new[3, :10] = (np.arange(10) % 8 + 1) / 8.0  # rank 10 <= width 16
    new_params = params._replace(q=jnp.asarray(q_new))
    engine.swap(new_params, t, t, touched_users=np.array([0]),
                touched_items=np.array([3]))
    assert engine._snap.stream_layout()[0].shape[2] == 16  # still compact
    fresh = ServingEngine(new_params, t, t, use_kernel=False, block_n=128)
    s0, i0 = fresh.topk(np.arange(20), 7)
    s1, i1 = engine.topk(np.arange(20), 7)
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
