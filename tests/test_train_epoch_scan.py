"""Parity + safety suite for the epoch-compiled training path.

The contract under test: ``mf.train_epoch_scan`` (one donated lax.scan per
epoch over packed device-resident batches) is *numerically equivalent* to
folding ``mf.train_step`` over the same batches from Python — for every row
optimizer, every variant, and the weighted/biased fused-kernel cases — and
the donation never lets stale buffers leak back into the caller.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import mf
from repro.data import build_user_history, synthetic_ratings
from repro.data import loader
from repro.kernels import fused_mf_sgd, ref
from repro.launch.mesh import make_mesh
from repro.optim.optimizers import RowOptimizer

OPTIMIZERS = ("sgd", "momentum", "adagrad", "adadelta", "adam")
# momentum compounds duplicate-row updates; a smaller lr keeps it stable
LR = {"sgd": 0.02, "momentum": 0.005, "adagrad": 0.05,
      "adadelta": 1.0, "adam": 0.005}

M, N, K = 120, 150, 16


@pytest.fixture(scope="module")
def packed():
    ds = synthetic_ratings(M, N, 6000, seed=1)
    return loader.pack_ratings(ds, 256)


def _fold_train_step(params, state, batches, *, opt, hist=None, t=0.04,
                     lr=0.05, use_fused_kernel=False):
    steps = batches["user"].shape[0]
    errs, works = [], []
    for i in range(steps):
        b = {key: v[i] for key, v in batches.items()}
        if hist is not None:
            b["hist"] = hist[b["user"]]
        params, state, m = mf.train_step(
            params, state, b, jnp.float32(t), jnp.float32(t),
            jnp.float32(lr), jnp.ones((K,)), opt=opt, lam=0.02,
            use_fused_kernel=use_fused_kernel,
        )
        errs.append(float(m["abs_err"]))
        works.append(float(m["work_fraction"]))
    return params, state, {"abs_err": np.mean(errs),
                           "work_fraction": np.mean(works)}


def _fresh(opt, variant="funk"):
    params = mf.init_params(
        jax.random.PRNGKey(0), M, N, K, variant=variant, global_mean=3.2
    )
    return params, mf.init_opt_state(params, opt)


def _assert_params_close(a, b, atol=1e-6):
    for name in a._fields:
        va, vb = getattr(a, name), getattr(b, name)
        if va is None:
            assert vb is None
            continue
        np.testing.assert_allclose(
            np.asarray(va), np.asarray(vb), atol=atol, rtol=0, err_msg=name
        )


@pytest.mark.parametrize("opt_name", OPTIMIZERS)
def test_scan_epoch_matches_per_batch_loop(packed, opt_name):
    opt = RowOptimizer(name=opt_name)
    batches = packed.epoch_batches(0, 0)
    lr = LR[opt_name]

    params, state = _fresh(opt)
    want_p, want_s, want_m = _fold_train_step(
        params, state, batches, opt=opt, lr=lr
    )
    params2, state2 = _fresh(opt)
    got_p, got_s, got_m = mf.train_epoch_scan(
        params2, state2, batches, jnp.float32(0.04), jnp.float32(0.04),
        jnp.float32(lr), jnp.ones((K,)), opt=opt, lam=0.02,
    )
    _assert_params_close(want_p, got_p)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=0
        ),
        want_s, got_s,
    )
    assert abs(want_m["abs_err"] - float(got_m["abs_err"])) < 1e-5
    assert abs(want_m["work_fraction"] - float(got_m["work_fraction"])) < 1e-5


@pytest.mark.parametrize("variant", ["bias", "svdpp"])
def test_scan_epoch_variants(packed, variant):
    ds = synthetic_ratings(M, N, 6000, seed=1)
    hist = (
        jnp.asarray(build_user_history(ds, 8)[0]) if variant == "svdpp" else None
    )
    opt = RowOptimizer(name="adagrad")
    batches = packed.epoch_batches(0, 3)

    params, state = _fresh(opt, variant)
    want_p, _, _ = _fold_train_step(params, state, batches, opt=opt, hist=hist)
    params2, state2 = _fresh(opt, variant)
    got_p, _, _ = mf.train_epoch_scan(
        params2, state2, batches, jnp.float32(0.04), jnp.float32(0.04),
        jnp.float32(0.05), jnp.ones((K,)), hist,
        opt=opt, lam=0.02,
    )
    _assert_params_close(want_p, got_p)


def test_scan_epoch_weighted_batches(packed):
    """A weight column in the packed batches rides through the scan."""
    opt = RowOptimizer(name="adagrad")
    batches = dict(packed.epoch_batches(0, 1))
    rng = np.random.default_rng(0)
    batches["weight"] = jnp.asarray(
        rng.uniform(0.0, 1.0, batches["rating"].shape).astype(np.float32)
    )
    params, state = _fresh(opt)
    want_p, _, _ = _fold_train_step(params, state, batches, opt=opt)
    params2, state2 = _fresh(opt)
    got_p, _, _ = mf.train_epoch_scan(
        params2, state2, batches, jnp.float32(0.04), jnp.float32(0.04),
        jnp.float32(0.05), jnp.ones((K,)), opt=opt, lam=0.02,
    )
    _assert_params_close(want_p, got_p)


@pytest.mark.parametrize("weighted", [False, True])
def test_fused_kernel_bias_weight_vs_ref(weighted):
    """The generalized kernel (biases + weight in-kernel, interpret mode)
    matches the pure-jnp reference bit-for-bit at f32."""
    rng = np.random.default_rng(2)
    b, k = 96, 24
    p = jnp.asarray(rng.normal(0, 0.1, (b, k)).astype(np.float32))
    q = jnp.asarray(rng.normal(0, 0.1, (b, k)).astype(np.float32))
    r = jnp.asarray(rng.uniform(1, 5, b).astype(np.float32))
    bu = jnp.asarray(rng.normal(0, 0.05, b).astype(np.float32))
    bi = jnp.asarray(rng.normal(0, 0.05, b).astype(np.float32))
    w = (
        jnp.asarray(rng.uniform(0, 1, b).astype(np.float32))
        if weighted else None
    )
    kw = dict(lr=0.05, lam=0.02, bias_u=bu, bias_i=bi, global_mean=3.1,
              weight=w)
    want = ref.fused_mf_sgd_ref(p, q, r, jnp.float32(0.06), jnp.float32(0.06),
                                **kw)
    got = fused_mf_sgd(p, q, r, 0.06, 0.06, block_b=32, **kw)
    for name, a, b_ in zip(("p", "q", "bu", "bi", "err"), want, got):
        np.testing.assert_allclose(
            np.asarray(b_), np.asarray(a), atol=1e-6, rtol=0, err_msg=name
        )


def test_fused_train_step_biased_weighted_matches_xla(packed):
    """use_fused_kernel=True now covers BiasSVD and weighted batches."""
    opt = RowOptimizer(name="sgd")
    batches = dict(packed.epoch_batches(0, 2))
    rng = np.random.default_rng(1)
    batches["weight"] = jnp.asarray(
        rng.uniform(0.0, 1.0, batches["rating"].shape).astype(np.float32)
    )
    b = {key: v[0] for key, v in batches.items()}
    params, state = _fresh(opt, "bias")
    args = (jnp.float32(0.04), jnp.float32(0.04), jnp.float32(0.02),
            jnp.ones((K,)))
    want_p, _, want_m = mf.train_step(
        params, state, b, *args, opt=opt, lam=0.02, use_fused_kernel=False
    )
    got_p, _, got_m = mf.train_step(
        params, state, b, *args, opt=opt, lam=0.02, use_fused_kernel=True
    )
    _assert_params_close(want_p, got_p)
    assert abs(float(want_m["abs_err"]) - float(got_m["abs_err"])) < 1e-5


def test_donation_safety(packed):
    """No use-after-donate: chained epochs only ever touch the returned
    arrays, and the donated inputs are really gone (when the backend honors
    donation) — reading them must not silently alias the new state."""
    opt = RowOptimizer(name="adagrad")
    params, state = _fresh(opt)
    params_copy = jax.tree_util.tree_map(jnp.copy, params)
    chain_p, chain_s = params, state
    for epoch in range(3):
        batches = packed.epoch_batches(0, epoch)
        chain_p, chain_s, metrics = mf.train_epoch_scan(
            chain_p, chain_s, batches, jnp.float32(0.04), jnp.float32(0.04),
            jnp.float32(0.05), jnp.ones((K,)), opt=opt, lam=0.02,
        )
    assert np.isfinite(float(metrics["abs_err"]))
    # the original buffers were either invalidated (donation honored) or left
    # intact (backend ignored the hint) — never mutated in place
    try:
        leaked = np.asarray(params.p)
    except RuntimeError:
        pass  # deleted by donation: any read after donate must raise
    else:
        np.testing.assert_array_equal(leaked, np.asarray(params_copy.p))
    # and the chained result must not alias the donated input
    assert not np.array_equal(np.asarray(chain_p.p), np.asarray(params_copy.p))


def test_eval_epoch_scan_matches_loop():
    ds = synthetic_ratings(M, N, 3000, seed=3)
    params = mf.init_params(jax.random.PRNGKey(1), M, N, K)
    t = jnp.float32(0.04)
    total = count = 0.0
    for b_np in loader.iterate_batches(ds, 512, shuffle=False,
                                       drop_remainder=False):
        b = {key: jnp.asarray(v) for key, v in b_np.items()}
        s, c = mf.eval_mae(params, b, t, t)
        total += float(s)
        count += float(c)
    packed_eval = loader.pack_eval_batches(ds, 512)
    tot, cnt = mf.eval_epoch_scan(params, packed_eval, t, t)
    assert abs(float(cnt) - count) < 1e-6
    assert abs(float(tot) - total) < 1e-3


def test_packed_epoch_batches_deterministic_and_complete(packed):
    a = packed.epoch_batches(5, 2)
    b = packed.epoch_batches(5, 2)
    np.testing.assert_array_equal(np.asarray(a["user"]), np.asarray(b["user"]))
    c = packed.epoch_batches(5, 3)
    assert not np.array_equal(np.asarray(a["user"]), np.asarray(c["user"]))
    # the (steps, B) arrays are a permutation prefix: no duplicate examples
    n = packed.num_examples
    flat_r = np.asarray(a["rating"]).ravel()
    assert flat_r.shape[0] == packed.num_steps * packed.batch_size <= n
    # reconstruct positions by matching (user, item) pairs is overkill; the
    # permutation property is visible through unique (user, item, rating)
    # triple counts not exceeding their dataset multiplicity
    flat = np.stack([
        np.asarray(a["user"]).ravel(), np.asarray(a["item"]).ravel()
    ], 1)
    pairs, counts = np.unique(flat, axis=0, return_counts=True)
    ds_pairs, ds_counts = np.unique(
        np.stack([np.asarray(packed.user), np.asarray(packed.item)], 1),
        axis=0, return_counts=True,
    )
    lookup = {tuple(p): c for p, c in zip(ds_pairs, ds_counts)}
    assert all(c <= lookup[tuple(p)] for p, c in zip(pairs, counts))


def test_route_batch_to_owner_shards_contract():
    from repro.distributed.sharding import route_batch_to_owner_shards

    rng = np.random.default_rng(0)
    users = rng.integers(0, 16, 37).astype(np.int32)
    items = rng.integers(0, 9, 37).astype(np.int32)
    ratings = rng.uniform(1, 5, 37).astype(np.float32)
    routed = route_batch_to_owner_shards(
        users, items, ratings, num_users=16, n_dp=4, pad_to_pow2=True
    )
    total = routed["user"].shape[0]
    assert total % 4 == 0
    length = total // 4
    assert (length & (length - 1)) == 0  # pow2
    for s in range(4):
        chunk_u = routed["user"][s * length : (s + 1) * length]
        assert np.all((chunk_u >= s * 4) & (chunk_u < (s + 1) * 4))
    # every real row survives exactly once, padding carries weight 0
    assert routed["weight"].sum() == 37
    real = routed["weight"] > 0
    got = np.stack([routed["user"][real], routed["item"][real],
                    routed["rating"][real]], 1)
    want = np.stack([users, items, ratings], 1)
    got_sorted = got[np.lexsort(got.T)]
    want_sorted = want[np.lexsort(want.T)]
    np.testing.assert_allclose(got_sorted, want_sorted)


def test_scan_shard_map_matches_single_device():
    """Sharded epoch scan == single-device epoch scan on the 4-device CI
    mesh (owner-routed batches, adagrad)."""
    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices (run under the 4-device CI mesh job)")
    from repro.distributed.sharding import route_batch_to_owner_shards

    mesh = make_mesh((2, 2), ("data", "model"))
    m, n, k, B, steps = 16, 8, 12, 16, 4
    rng = np.random.default_rng(0)
    routed_steps = []
    plain_steps = []
    for _ in range(steps):
        users = rng.integers(0, m, B).astype(np.int32)
        items = rng.integers(0, n, B).astype(np.int32)
        ratings = rng.uniform(1, 5, B).astype(np.float32)
        plain_steps.append({"user": users, "item": items, "rating": ratings,
                            "weight": np.ones(B, np.float32)})
        routed_steps.append(route_batch_to_owner_shards(
            users, items, ratings, num_users=m, n_dp=2
        ))
    lengths = {r["user"].shape[0] for r in routed_steps}
    length = max(lengths)
    for r in routed_steps:  # repad to a common (steps, L) stack
        pad = length - r["user"].shape[0]
        if pad:
            half = r["user"].shape[0] // 2
            for key in r:
                fill = (
                    np.repeat([0, m // 2], pad // 2 + 1)[:pad]
                    if key == "user" else np.zeros(pad, r[key].dtype)
                )
                r[key] = np.concatenate(
                    [r[key][:half], fill[: pad // 2], r[key][half:],
                     fill[pad // 2 :]]
                )
    routed = {
        key: jnp.asarray(np.stack([r[key] for r in routed_steps]))
        for key in routed_steps[0]
    }
    plain = {
        key: jnp.asarray(np.stack([b[key] for b in plain_steps]))
        for key in plain_steps[0]
    }

    opt = RowOptimizer(name="adagrad")
    params = mf.init_params(jax.random.PRNGKey(0), m, n, k)
    state = mf.init_opt_state(params, opt)
    want_p, want_s, want_m = mf.train_epoch_scan(
        params, state, plain, jnp.float32(0.05), jnp.float32(0.05),
        jnp.float32(0.05), jnp.ones((k,)), opt=opt, lam=0.02,
    )
    params2 = mf.init_params(jax.random.PRNGKey(0), m, n, k)
    state2 = mf.init_opt_state(params2, opt)
    with jax.sharding.set_mesh(mesh):
        got_p, got_s, got_m = mf.train_epoch_scan_shard_map(
            params2, state2, routed, 0.05, 0.05, lr=0.05, lam=0.02,
            opt_name="adagrad", mesh=mesh.abstract_mesh,
        )
    np.testing.assert_allclose(np.asarray(want_p.p), np.asarray(got_p.p),
                               atol=2e-7, rtol=0)
    np.testing.assert_allclose(np.asarray(want_p.q), np.asarray(got_p.q),
                               atol=2e-7, rtol=0)
    np.testing.assert_allclose(np.asarray(want_s.q["acc"]),
                               np.asarray(got_s.q["acc"]), atol=2e-7, rtol=0)
    assert abs(float(want_m["abs_err"]) - float(got_m["abs_err"])) < 1e-5


def test_momentum_optimizer_learns(packed):
    opt = RowOptimizer(name="momentum")
    params, state = _fresh(opt)
    first = None
    for epoch in range(4):
        params, state, m = mf.train_epoch_scan(
            params, state, packed.epoch_batches(0, epoch),
            jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.002),
            jnp.ones((K,)), opt=opt, lam=0.02,
        )
        if first is None:
            first = float(m["abs_err"])
    assert float(m["abs_err"]) < first

def test_route_batch_rejects_out_of_range_users():
    from repro.distributed.sharding import route_batch_to_owner_shards

    with pytest.raises(ValueError, match="grow the tables"):
        route_batch_to_owner_shards(
            np.asarray([20, 3]), np.asarray([1, 2]),
            np.asarray([4.0, 5.0], np.float32), num_users=16, n_dp=4,
        )


def test_shard_map_rejects_unknown_optimizer():
    with pytest.raises(ValueError, match="sgd and adagrad only"):
        mf.train_epoch_scan_shard_map(
            None, None, {}, 0.0, 0.0, lr=0.05, lam=0.02,
            opt_name="adam", mesh=object(),
        )
