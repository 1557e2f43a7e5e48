"""Differential-oracle campaign for the implicit / BPR workloads.

Three independent oracles pin the new objectives to the pruned update the
paper defines:

* ``jax.grad`` of the masked loss (masks stop-gradiented, exactly as the
  steps treat them) — the analytic gradients in ``mf.train_step`` and
  ``workloads.bpr.bpr_train_step`` must BE that gradient;
* the NumPy transcription ``kernels.ref.bpr_step_ref`` on 1/8-grid
  factors — framework-independent semantics, scatter-add duplicates and
  all;
* the one-step SGD oracle ``kernels.ref.sgd_step_ref`` with the
  confidence as its weight column, for the confidence-weighted objective.

Plus hypothesis property tests on the WALS confidence contract: weight 0
is bitwise inert, larger confidence moves factors further.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401
from sgd_step import train_step_on_rows, weighted_mean_abs

from repro.core import mf
from repro.core.ranks import effective_ranks, rank_mask
from repro.data import ratings as rdata
from repro.kernels import ref
from repro.optim.optimizers import RowOptimizer
from repro.workloads import (
    BPRSampler,
    binarize_positives,
    bpr_epoch_scan,
    bpr_train_step,
    confidence_weights,
    implicit_dataset,
)

K = 8
M, N = 24, 32


def _grid(rng, shape):
    """f32 multiples of 1/8 in [-2, 2]: float ops on them are exact."""
    return (rng.integers(-16, 17, shape) / 8.0).astype(np.float32)


def _grid_params(seed, variant="funk"):
    rng = np.random.default_rng(seed)
    p = jnp.asarray(_grid(rng, (M, K)))
    q = jnp.asarray(_grid(rng, (N, K)))
    if variant == "funk":
        return mf.MFParams(p, q, None, None, None, None)
    return mf.MFParams(
        p, q,
        user_bias=jnp.asarray(_grid(rng, (M, 1))),
        item_bias=jnp.asarray(_grid(rng, (N, 1))),
        global_mean=jnp.float32(0.5),
        implicit=None,
    )


def _triples(seed, b=40):
    """Random (user, pos, neg) with guaranteed duplicates and a pos==neg."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, M, b).astype(np.int32)
    i = rng.integers(0, N, b).astype(np.int32)
    j = rng.integers(0, N, b).astype(np.int32)
    u[1], i[1], j[1] = u[0], i[0], j[0]   # duplicated triple
    j[2] = i[2]                           # pos == neg
    return jnp.asarray(u), jnp.asarray(i), jnp.asarray(j)


ARGS = (jnp.float32(0.25), jnp.float32(0.25))   # t_p, t_q on the grid
LR, LAM = 0.5, 0.25                              # grid-friendly dyadics


# -- implicit dataset construction -----------------------------------------

def _log(seed=0, n=200):
    return rdata.synthetic_ratings(
        num_users=M, num_items=N, num_ratings=n, seed=seed
    )


def test_confidence_weights():
    w = confidence_weights(np.array([0.0, 1.0, 5.0]), alpha=40.0)
    np.testing.assert_array_equal(w, np.float32([1.0, 41.0, 201.0]))
    assert w.dtype == np.float32


def test_implicit_dataset_geometry_and_weights():
    ds = _log()
    out, weight = implicit_dataset(ds, alpha=10.0, negatives=3, seed=0)
    assert len(out) == len(ds) * 4
    assert weight.shape == (len(out),)
    assert (out.num_users, out.num_items) == (ds.num_users, ds.num_items)
    assert (out.rating_min, out.rating_max) == (0.0, 1.0)
    n = len(ds)
    # positives first: preference 1, confidence 1 + alpha*r
    np.testing.assert_array_equal(out.rating[:n], np.ones(n, np.float32))
    np.testing.assert_array_equal(
        weight[:n], confidence_weights(ds.rating, 10.0)
    )
    # negatives: preference 0 at floor confidence 1
    np.testing.assert_array_equal(out.rating[n:], np.zeros(3 * n, np.float32))
    np.testing.assert_array_equal(weight[n:], np.ones(3 * n, np.float32))


def test_implicit_negatives_avoid_positives_and_are_deterministic():
    ds = _log()
    pos = {(int(u), int(i)) for u, i in zip(ds.user, ds.item)}
    out, _ = implicit_dataset(ds, negatives=2, seed=3)
    n = len(ds)
    clashes = sum(
        (int(u), int(i)) in pos
        for u, i in zip(out.user[n:], out.item[n:])
    )
    assert clashes == 0  # catalog is much larger than any positive set
    out2, w2 = implicit_dataset(ds, negatives=2, seed=3)
    np.testing.assert_array_equal(out.item, out2.item)
    out3, _ = implicit_dataset(ds, negatives=2, seed=4)
    assert not np.array_equal(out.item[n:], out3.item[n:])


def test_binarize_positives():
    ds = _log()
    out = binarize_positives(ds)
    np.testing.assert_array_equal(out.user, ds.user)
    np.testing.assert_array_equal(out.item, ds.item)
    np.testing.assert_array_equal(out.rating, np.ones(len(ds), np.float32))
    assert (out.rating_min, out.rating_max) == (0.0, 1.0)


def test_implicit_dataset_rejects_negative_negatives():
    with pytest.raises(ValueError, match="negatives"):
        implicit_dataset(_log(), negatives=-1)


# -- oracle 1: jax.grad of the masked loss ---------------------------------

def _weighted_batch(seed, b=48):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, M, b).astype(np.int32)
    i = rng.integers(0, N, b).astype(np.int32)
    u[1], i[1] = u[0], i[0]   # duplicate (u, i) row: scatter-add semantics
    return {
        "user": jnp.asarray(u),
        "item": jnp.asarray(i),
        "rating": jnp.asarray(rng.integers(0, 2, b).astype(np.float32)),
        "weight": jnp.asarray(
            confidence_weights(rng.integers(0, 2, b), alpha=4.0)
        ),
    }


def test_weighted_implicit_step_is_gradient_of_masked_loss():
    """The WALS update (confidence riding batch["weight"]) must equal one
    plain-SGD descent step on sum_b c_b*(0.5*err² + 0.5*lam*||rows∘m||²)
    with the pair masks held constant — pinned via jax.grad."""
    params = _grid_params(11)
    opt = RowOptimizer(name="sgd")
    state = mf.init_opt_state(params, opt)
    batch = _weighted_batch(12)
    t_p, t_q = ARGS

    def loss(p, q):
        x, y = p[batch["user"]], q[batch["item"]]
        m = jax.lax.stop_gradient(
            rank_mask(
                jnp.minimum(effective_ranks(x, t_p), effective_ranks(y, t_q)),
                K,
            )
        )
        err = batch["rating"] - jnp.sum(x * y * m, axis=-1)
        reg = jnp.sum(jnp.square(x * m), -1) + jnp.sum(jnp.square(y * m), -1)
        return jnp.sum(batch["weight"] * (0.5 * err**2 + 0.5 * LAM * reg))

    g_p, g_q = jax.grad(loss, argnums=(0, 1))(params.p, params.q)
    new_params, _, _ = mf.train_step(
        params, state, batch, t_p, t_q, jnp.float32(LR), jnp.ones((K,)),
        opt=opt, lam=LAM,
    )
    np.testing.assert_allclose(
        np.asarray(new_params.p), np.asarray(params.p - LR * g_p),
        rtol=0, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(new_params.q), np.asarray(params.q - LR * g_q),
        rtol=0, atol=1e-5,
    )


@pytest.mark.parametrize("variant", ["funk", "bias"])
def test_bpr_step_is_gradient_of_masked_loss(variant):
    """bpr_train_step must be exact SGD on the masked pairwise loss
    -log σ(s_ui - s_uj) + 0.5·lam·(own-rank-masked norms), masks constant."""
    params = _grid_params(21, variant)
    opt = RowOptimizer(name="sgd")
    state = mf.init_opt_state(params, opt)
    u, i, j = _triples(22)
    t_p, t_q = ARGS

    def loss(p, q, bias):
        x, yi, yj = p[u], q[i], q[j]
        r_u = effective_ranks(x, t_p)
        r_i = effective_ranks(yi, t_q)
        r_j = effective_ranks(yj, t_q)
        sg = jax.lax.stop_gradient
        m_ui = sg(rank_mask(jnp.minimum(r_u, r_i), K))
        m_uj = sg(rank_mask(jnp.minimum(r_u, r_j), K))
        s_ui = jnp.sum(x * yi * m_ui, -1)
        s_uj = jnp.sum(x * yj * m_uj, -1)
        reg = (
            jnp.sum(jnp.square(x * sg(rank_mask(r_u, K))), -1)
            + jnp.sum(jnp.square(yi * sg(rank_mask(r_i, K))), -1)
            + jnp.sum(jnp.square(yj * sg(rank_mask(r_j, K))), -1)
        )
        if bias is not None:
            s_ui = s_ui + bias[i, 0]
            s_uj = s_uj + bias[j, 0]
            reg = reg + jnp.square(bias[i, 0]) + jnp.square(bias[j, 0])
        diff = s_ui - s_uj
        nll = jnp.log1p(jnp.exp(-jnp.abs(diff))) + jnp.maximum(-diff, 0.0)
        return jnp.sum(nll + 0.5 * LAM * reg)

    grads = jax.grad(loss, argnums=(0, 1, 2))(
        params.p, params.q, params.item_bias
    )
    new_params, _, _ = bpr_train_step(
        params, state, {"user": u, "pos": i, "neg": j},
        t_p, t_q, jnp.float32(LR), jnp.ones((K,)), opt=opt, lam=LAM,
    )
    np.testing.assert_allclose(
        np.asarray(new_params.p), np.asarray(params.p - LR * grads[0]),
        rtol=0, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(new_params.q), np.asarray(params.q - LR * grads[1]),
        rtol=0, atol=1e-5,
    )
    if variant == "bias":
        np.testing.assert_allclose(
            np.asarray(new_params.item_bias),
            np.asarray(params.item_bias - LR * grads[2]),
            rtol=0, atol=1e-5,
        )
        # user bias and global mean cancel in the pairwise diff: untouched
        np.testing.assert_array_equal(
            np.asarray(new_params.user_bias), np.asarray(params.user_bias)
        )


# -- oracle 2: NumPy reference on the 1/8 grid -----------------------------

@pytest.mark.parametrize("variant", ["funk", "bias"])
@pytest.mark.parametrize("weighted", [False, True])
def test_bpr_step_matches_numpy_reference(variant, weighted):
    params = _grid_params(31, variant)
    opt = RowOptimizer(name="sgd")
    state = mf.init_opt_state(params, opt)
    u, i, j = _triples(32)
    rng = np.random.default_rng(33)
    w = (
        rng.integers(0, 3, u.shape[0]).astype(np.float32)
        if weighted else None
    )
    batch = {"user": u, "pos": i, "neg": j}
    if weighted:
        batch["weight"] = jnp.asarray(w)
    t_p, t_q = ARGS

    bias = (
        None if variant == "funk"
        else np.asarray(params.item_bias)[:, 0]
    )
    want_p, want_q, want_b, want_loss = ref.bpr_step_ref(
        np.asarray(params.p), np.asarray(params.q),
        np.asarray(u), np.asarray(i), np.asarray(j),
        float(t_p), float(t_q), lr=LR, lam=LAM, item_bias=bias, weight=w,
    )
    got, _, metrics = bpr_train_step(
        params, state, batch, t_p, t_q, jnp.float32(LR), jnp.ones((K,)),
        opt=opt, lam=LAM,
    )
    np.testing.assert_allclose(np.asarray(got.p), want_p, rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.q), want_q, rtol=0, atol=1e-6)
    if variant == "bias":
        np.testing.assert_allclose(
            np.asarray(got.item_bias)[:, 0], want_b, rtol=0, atol=1e-6
        )
    assert abs(float(metrics["abs_err"]) - want_loss) < 1e-6
    # rows no triple touches stay bitwise identical
    touched_u = set(np.asarray(u).tolist())
    touched_q = set(np.asarray(i).tolist()) | set(np.asarray(j).tolist())
    for row in range(M):
        if row not in touched_u:
            np.testing.assert_array_equal(
                np.asarray(got.p[row]), np.asarray(params.p[row])
            )
    for row in range(N):
        if row not in touched_q:
            np.testing.assert_array_equal(
                np.asarray(got.q[row]), np.asarray(params.q[row])
            )


def test_bpr_threshold_zero_is_dense():
    """Rate 0 ≡ dense BPR: masks all-ones, bitwise-same as the unmasked
    reference run at thresholds 0."""
    params = _grid_params(41)
    opt = RowOptimizer(name="sgd")
    state = mf.init_opt_state(params, opt)
    u, i, j = _triples(42)
    want_p, want_q, _, _ = ref.bpr_step_ref(
        np.asarray(params.p), np.asarray(params.q),
        np.asarray(u), np.asarray(i), np.asarray(j),
        0.0, 0.0, lr=LR, lam=LAM,
    )
    got, _, metrics = bpr_train_step(
        params, state, {"user": u, "pos": i, "neg": j},
        jnp.float32(0.0), jnp.float32(0.0), jnp.float32(LR),
        jnp.ones((K,)), opt=opt, lam=LAM,
    )
    np.testing.assert_allclose(np.asarray(got.p), want_p, rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.q), want_q, rtol=0, atol=1e-6)
    assert float(metrics["work_fraction"]) == 1.0


# -- oracle 3: the one-step SGD oracle, weighted objective ----------------

@pytest.mark.parametrize("variant", ["funk", "bias"])
def test_implicit_confidence_step_matches_sgd_ref(variant):
    """The confidence-weighted (implicit) batch through the SGD step matches
    the one-step oracle with the confidence as its weight column."""
    params = _grid_params(51, variant)
    rng = np.random.default_rng(52)
    b = 16
    r = jnp.asarray(rng.integers(0, 2, b).astype(np.float32))
    c = jnp.asarray(confidence_weights(rng.integers(0, 2, b), alpha=4.0))
    kw = dict(lr=LR, lam=LAM, weight=c)
    if variant == "bias":
        kw.update(bias_u=params.user_bias[:b, 0],
                  bias_i=params.item_bias[:b, 0],
                  global_mean=params.global_mean)
    p, q = params.p[:b], params.q[:b]
    *want, want_err = ref.sgd_step_ref(p, q, r, *ARGS, **kw)
    *got, got_abs_err = train_step_on_rows(p, q, r, ARGS[0], **kw)
    for name, a, g in zip(("p", "q", "user_bias", "item_bias"), want, got):
        if a is None:
            assert g is None
            continue
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(a), rtol=0, atol=1e-6, err_msg=name
        )
    assert abs(float(got_abs_err) - float(weighted_mean_abs(want_err, c))) < 1e-5


# -- hypothesis: the confidence-weight contract ----------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from(["sgd", "adagrad"]))
def test_weight_zero_rows_are_bitwise_inert(seed, opt_name):
    """Confidence 0 must be indistinguishable from the example not existing
    — bitwise, on params AND optimizer state (sgd/adagrad contract)."""
    rng = np.random.default_rng(seed)
    params = _grid_params(rng.integers(0, 2**31))
    opt = RowOptimizer(name=opt_name)
    state = mf.init_opt_state(params, opt)
    b = 16
    # distinct users/items per row so zeroed rows share nothing with live ones
    u = jnp.asarray(rng.permutation(M)[:b].astype(np.int32))
    i = jnp.asarray(rng.permutation(N)[:b].astype(np.int32))
    r = jnp.asarray(rng.integers(0, 2, b).astype(np.float32))
    keep = rng.integers(0, 2, b).astype(np.float32)
    batch = {"user": u, "item": i, "rating": r, "weight": jnp.asarray(keep)}
    args = (*ARGS, jnp.float32(0.5), jnp.ones((K,)))
    new_params, new_state, _ = mf.train_step(
        params, state, batch, *args, opt=opt, lam=LAM
    )
    dead = np.flatnonzero(keep == 0.0)
    for row in dead:
        np.testing.assert_array_equal(
            np.asarray(new_params.p[u[row]]), np.asarray(params.p[u[row]])
        )
        np.testing.assert_array_equal(
            np.asarray(new_params.q[i[row]]), np.asarray(params.q[i[row]])
        )
        for key, val in new_state.p.items():
            np.testing.assert_array_equal(
                np.asarray(val[u[row]]), np.asarray(state.p[key][u[row]])
            )


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(0.0, 4.0), st.floats(0.1, 8.0))
def test_monotone_confidence_moves_factors_more(seed, w_lo, w_extra):
    """For a single example under SGD, a strictly larger confidence never
    moves any factor coordinate less (|Δ| is elementwise non-decreasing in
    the weight — the update is linear in it)."""
    rng = np.random.default_rng(seed)
    params = _grid_params(rng.integers(0, 2**31))
    opt = RowOptimizer(name="sgd")
    state = mf.init_opt_state(params, opt)
    u = jnp.asarray(rng.integers(0, M, 1).astype(np.int32))
    i = jnp.asarray(rng.integers(0, N, 1).astype(np.int32))
    r = jnp.asarray(rng.integers(0, 2, 1).astype(np.float32))
    args = (*ARGS, jnp.float32(0.01), jnp.ones((K,)))

    def delta(w):
        batch = {"user": u, "item": i, "rating": r,
                 "weight": jnp.full((1,), w, jnp.float32)}
        out, _, _ = mf.train_step(
            params, state, batch, *args, opt=opt, lam=LAM
        )
        return (
            np.abs(np.asarray(out.p[u[0]] - params.p[u[0]])),
            np.abs(np.asarray(out.q[i[0]] - params.q[i[0]])),
        )

    dp_lo, dq_lo = delta(w_lo)
    dp_hi, dq_hi = delta(w_lo + w_extra)
    assert (dp_hi >= dp_lo - 1e-9).all()
    assert (dq_hi >= dq_lo - 1e-9).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_bpr_weight_zero_triples_are_bitwise_inert(seed):
    rng = np.random.default_rng(seed)
    params = _grid_params(rng.integers(0, 2**31))
    opt = RowOptimizer(name="sgd")
    state = mf.init_opt_state(params, opt)
    b = 8
    u = jnp.asarray(rng.permutation(M)[:b].astype(np.int32))
    # disjoint pos/neg pools so a dead triple shares no row with a live one
    perm = rng.permutation(N)
    i, j = jnp.asarray(perm[:b].astype(np.int32)), jnp.asarray(
        perm[b:2 * b].astype(np.int32)
    )
    keep = rng.integers(0, 2, b).astype(np.float32)
    new_params, _, _ = bpr_train_step(
        params, state,
        {"user": u, "pos": i, "neg": j, "weight": jnp.asarray(keep)},
        *ARGS, jnp.float32(0.5), jnp.ones((K,)), opt=opt, lam=LAM,
    )
    for row in np.flatnonzero(keep == 0.0):
        np.testing.assert_array_equal(
            np.asarray(new_params.p[u[row]]), np.asarray(params.p[u[row]])
        )
        np.testing.assert_array_equal(
            np.asarray(new_params.q[i[row]]), np.asarray(params.q[i[row]])
        )
        np.testing.assert_array_equal(
            np.asarray(new_params.q[j[row]]), np.asarray(params.q[j[row]])
        )


# -- sampler & epoch scan ---------------------------------------------------

def test_bpr_sampler_deterministic_and_rejects_positives():
    ds = _log(n=150)
    sampler = BPRSampler(ds, batch_size=32, seed=5)
    t1 = sampler.epoch_triples(2)
    t2 = BPRSampler(ds, batch_size=32, seed=5).epoch_triples(2)
    for key in ("user", "pos", "neg"):
        np.testing.assert_array_equal(np.asarray(t1[key]), np.asarray(t2[key]))
    t3 = sampler.epoch_triples(3)
    assert not np.array_equal(np.asarray(t1["neg"]), np.asarray(t3["neg"]))
    pos = {(int(u), int(i)) for u, i in zip(ds.user, ds.item)}
    users = np.asarray(t1["user"]).ravel()
    negs = np.asarray(t1["neg"]).ravel()
    assert sum((int(u), int(n)) in pos for u, n in zip(users, negs)) == 0
    # every sampled pos really is one of the user's interactions
    poss = np.asarray(t1["pos"]).ravel()
    assert all((int(u), int(i)) in pos for u, i in zip(users, poss))


def test_bpr_sampler_oversized_batch_raises():
    ds = _log(n=20)
    sampler = BPRSampler(ds, batch_size=10_000, seed=0)
    assert sampler.batch_size == len(ds)   # clamped
    assert sampler.num_steps == 1
    empty = rdata.RatingsDataset(
        user=np.zeros(0, np.int32), item=np.zeros(0, np.int32),
        rating=np.zeros(0, np.float32), num_users=M, num_items=N,
    )
    with pytest.raises(ValueError, match="exceeds"):
        BPRSampler(empty, batch_size=4).epoch_triples(0)


def test_bpr_epoch_scan_matches_folded_steps():
    ds = _log(n=96)
    sampler = BPRSampler(ds, batch_size=24, seed=9)
    triples = sampler.epoch_triples(0)
    opt = RowOptimizer(name="adagrad")
    args = (*ARGS, jnp.float32(0.05), jnp.ones((K,)))

    params = _grid_params(61)
    state = mf.init_opt_state(params, opt)
    steps = triples["user"].shape[0]
    want_p, want_s = params, state
    for step in range(steps):
        batch = {key: val[step] for key, val in triples.items()}
        want_p, want_s, _ = bpr_train_step(
            want_p, want_s, batch, *args, opt=opt, lam=LAM
        )

    params2 = _grid_params(61)
    state2 = mf.init_opt_state(params2, opt)
    got_p, _, metrics = bpr_epoch_scan(
        params2, state2, triples, *args, opt=opt, lam=LAM
    )
    np.testing.assert_allclose(
        np.asarray(got_p.p), np.asarray(want_p.p), rtol=0, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(got_p.q), np.asarray(want_p.q), rtol=0, atol=1e-6
    )
    assert np.isfinite(float(metrics["abs_err"]))


# -- trainer integration ----------------------------------------------------

def _split(seed=0):
    ds = rdata.synthetic_ratings(
        num_users=40, num_items=50, num_ratings=500, seed=seed
    )
    return rdata.train_test_split(ds, test_fraction=0.2, seed=1)


def test_trainer_implicit_objective_end_to_end():
    from repro.core.trainer import DPMFTrainer, TrainConfig

    cfg = TrainConfig(
        k=K, epochs=2, batch_size=128, lr=0.02, lam=0.02, pruning_rate=0.3,
        objective="implicit", implicit_alpha=4.0, implicit_negatives=2,
        seed=0, ranking_topk=5,
    )
    tr, te = _split()
    trainer = DPMFTrainer(cfg, tr, te)
    assert len(trainer.train_ds) == len(tr) * 3
    assert set(np.unique(trainer.train_ds.rating)) <= {0.0, 1.0}
    assert trainer._train_weight is not None
    history = trainer.run()
    assert len(history) == 2
    assert all(np.isfinite(rec.test_mae) for rec in history)
    report = trainer.evaluate_ranking()
    assert report is not None and np.isfinite(report.ndcg)
    # pruning engaged after calibration
    assert history[1].work_fraction < 1.0


def test_trainer_bpr_objective_end_to_end():
    from repro.core.trainer import DPMFTrainer, TrainConfig

    cfg = TrainConfig(
        k=K, epochs=3, batch_size=64, lr=0.05, lam=0.02, pruning_rate=0.3,
        objective="bpr", seed=0, ranking_topk=5,
    )
    tr, te = _split()
    trainer = DPMFTrainer(cfg, tr, te)
    history = trainer.run()
    # abs_err carries the BPR loss: it must go down from the 0.693 start
    assert history[0].train_abs_err < float(np.log(2.0)) + 0.05
    assert history[-1].train_abs_err < history[0].train_abs_err
    # rating error is undefined for a pairwise objective
    assert all(np.isnan(rec.test_mae) for rec in history)
    assert np.isnan(trainer.evaluate())
    report = trainer.evaluate_ranking()
    assert report is not None and report.hr > 0.0


def test_trainer_objective_validation():
    from repro.core.trainer import DPMFTrainer, TrainConfig

    tr, te = _split()
    with pytest.raises(ValueError, match="unknown objective"):
        DPMFTrainer(TrainConfig(objective="pointwise"), tr, te)
    with pytest.raises(ValueError, match="svdpp"):
        DPMFTrainer(TrainConfig(objective="bpr", variant="svdpp"), tr, te)
    with pytest.raises(ValueError, match="train_ds"):
        DPMFTrainer(TrainConfig(objective="bpr"), None, te)
