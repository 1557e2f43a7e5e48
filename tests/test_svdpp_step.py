"""One SVD++ training step against Koren's update (KDD 2008, section 4),
computed by hand in float64 on three ratings of two users.

The step is a minibatch: every rating's gradient reads the parameters as
they were before the batch, and the updates of a row add up.  Plain SGD,
no pruning (thresholds 0), so each update is -lr times the gradient:

    e    = r - (mu + b_u + b_i + q_i . z_u),  z_u = p_u + |N(u)|^-1/2 sum_{j in N(u)} y_j
    p_u += lr (e q_i - lam p_u)
    q_i += lr (e z_u - lam q_i)
    y_j += lr (e |N(u)|^-1/2 q_i - lam y_j)   for j in N(u)
    b_u += lr (e - lam b_u),  b_i += lr (e - lam b_i)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mf
from repro.data import build_user_history, synthetic_ratings
from repro.optim.optimizers import DOUBLING_MAX, RowOptimizer

LR, LAM, MU = 0.1, 0.02, 3.0
P = np.array([[0.1, 0.2], [0.3, -0.1]])
Q = np.array([[0.2, 0.1], [-0.1, 0.3], [0.05, 0.2]])
Y = np.array([[0.1, 0.0], [0.0, 0.2], [0.3, 0.1], [0.0, 0.0]])   # row 3 pads
B_U, B_I = np.array([0.5, -0.2]), np.array([0.1, 0.0, -0.3])
RATINGS = [(0, 0, 4.0), (0, 1, 3.0), (1, 2, 5.0)]
N = {0: [0, 1], 1: [2]}
HIST = np.array([[0, 1], [2, 3]], np.int32)


def _koren():
    p, q, y, b_u, b_i = P.copy(), Q.copy(), Y.copy(), B_U.copy(), B_I.copy()
    for u, i, r in RATINGS:
        norm = len(N[u]) ** -0.5
        z = P[u] + norm * Y[N[u]].sum(axis=0)
        e = r - (MU + B_U[u] + B_I[i] + z @ Q[i])
        p[u] += LR * (e * Q[i] - LAM * P[u])
        q[i] += LR * (e * z - LAM * Q[i])
        for j in N[u]:
            y[j] += LR * (e * norm * Q[i] - LAM * Y[j])
        b_u[u] += LR * (e - LAM * B_U[u])
        b_i[i] += LR * (e - LAM * B_I[i])
    return p, q, y, b_u, b_i


def test_svdpp_step_is_korens_update():
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    params = mf.MFParams(f32(P), f32(Q), f32(B_U[:, None]), f32(B_I[:, None]),
                         f32(MU), f32(Y))
    opt = RowOptimizer(name="sgd")
    users = np.array([u for u, _, _ in RATINGS], np.int32)
    batch = {"user": jnp.asarray(users),
             "item": jnp.asarray([i for _, i, _ in RATINGS], jnp.int32),
             "rating": f32([r for _, _, r in RATINGS]),
             "hist": jnp.asarray(HIST[users])}
    new, _, _ = mf.train_step(
        params, mf.init_opt_state(params, opt), batch, f32(0.0), f32(0.0),
        f32(LR), jnp.ones((2,), jnp.float32), opt=opt, lam=LAM,
    )
    got = (new.p, new.q, new.implicit, new.user_bias[:, 0], new.item_bias[:, 0])
    # float32 arithmetic on values of order 1: a few ulp of 1
    for g, w in zip(got, _koren()):
        np.testing.assert_allclose(np.asarray(g), w, rtol=0, atol=1e-6)
    # the penalty on the whole user vector z_u moves p_0 by lr*lam*(z_0 -
    # p_0) per rating, 2.8e-4 here: far outside that tolerance
    z0 = P[0] + (Y[0] + Y[1]) / np.sqrt(2)
    assert np.abs(2 * LR * LAM * (z0 - P[0])).max() > 100 * 1e-6


# ---------------------------------------------------------------------------
# the implicit side on the step's live history slots, packed to a capacity
# ---------------------------------------------------------------------------

K = 8


def _svdpp_problem(num_users, num_items, max_hist, seed=0, optimizer="adagrad"):
    """Ratings, their SVD++ histories, a model and its optimizer state."""
    ds = synthetic_ratings(num_users, num_items, 7 * num_users, seed=seed)
    hist, _ = build_user_history(ds, max_hist)
    opt = RowOptimizer(name=optimizer)
    params = mf.init_params(jax.random.PRNGKey(seed), num_users, num_items, K,
                            variant="svdpp", global_mean=3.0)
    return ds, hist, params, mf.init_opt_state(params, opt), opt


def _in_place_slots(hist, num_items):
    """Every one of the (B, H) ``hist``'s slots where it stands, padding
    included: the full-width layout that the packing takes the padding out
    of (a padding slot reads Y's zero row and is masked out)."""
    b, h = hist.shape
    flat = hist.reshape(-1)
    pos = jnp.arange(b * h)
    return mf._Slots(item=flat, rating=pos // h, head=pos % h == 0,
                     live=flat < num_items, last=jnp.arange(b) * h + h - 1,
                     count=jnp.sum(flat < num_items))


@functools.partial(jax.jit, static_argnames=("opt", "lam", "capacity"))
def _full_width_step(params, state, batch, t_p, t_q, lr, dim_mask, *, opt, lam,
                     capacity):
    """The step over all B*H slots in place, with no packing."""
    del capacity
    slots = _in_place_slots(batch["hist"], params.implicit.shape[0] - 1)
    return mf._masked_step(params, state, batch, t_p, t_q, lr, dim_mask, slots,
                           opt=opt, lam=lam)


def _steps(params, state, opt, ds, hist, batch, capacity, steps=3, weight=False,
           step=mf.train_step):
    """``steps`` pruned Adagrad steps over the first batches of ``ds``."""
    metrics = []
    rng = np.random.default_rng(1)
    for s in range(steps):
        sl = slice(s * batch, (s + 1) * batch)
        users = ds.user[sl]
        b = {"user": jnp.asarray(users), "item": jnp.asarray(ds.item[sl]),
             "rating": jnp.asarray(ds.rating[sl]), "hist": jnp.asarray(hist[users])}
        if weight:
            b["weight"] = jnp.asarray(rng.uniform(0, 1, batch).astype(np.float32))
        params, state, m = step(
            params, state, b, jnp.float32(0.04), jnp.float32(0.04),
            jnp.float32(0.05), jnp.ones((K,), jnp.float32), opt=opt, lam=0.02,
            capacity=capacity,
        )
        metrics.append({key: float(v) for key, v in m.items()})
    return params, state, metrics


def _assert_states_close(got, want, exact=False):
    # float32 sums of up to a few thousand terms in another order: a few
    # hundred ulp of the largest entry
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if exact:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)


def _live_slots(hist, users, num_items):
    return int(np.sum(hist[users] < num_items))


@pytest.mark.parametrize(
    "num_users,max_hist,batch",
    [(300, 32, 128),      # 1,024 packed slots: the run sums double
     (2000, 32, 4096)],   # 32,768 packed slots, as at the BookCrossings
                          # cell: past DOUBLING_MAX, a segmented scan
    ids=["doubling", "segmented_scan"],
)
def test_packed_step_matches_full_width_step(num_users, max_hist, batch):
    """P, Q, Y, the biases, every Adagrad table and the step metrics of
    steps whose implicit side runs on the packed live slots match the
    steps packed to all B*H slots, whose sums run in the same order."""
    ds, hist, params, state, opt = _svdpp_problem(num_users, 2 * num_users, max_hist)
    sizes = np.sum(hist < ds.num_items, axis=1)[ds.user]
    capacity = mf.implicit_capacity(sizes, batch, max_hist)
    assert capacity <= batch * max_hist // 4
    assert DOUBLING_MAX < capacity or batch * max_hist <= DOUBLING_MAX
    full = _steps(params, state, opt, ds, hist, batch, None)
    packed = _steps(params, state, opt, ds, hist, batch, capacity)
    _assert_states_close(packed[:2], full[:2])
    for got, want in zip(packed[2], full[2]):
        assert got.pop("implicit_compact_share") == 1.0
        assert want.pop("implicit_compact_share") == 0.0
        assert got == pytest.approx(want, rel=1e-5)


def _scatter_rows(hist, seed=3):
    """``hist`` with each row's slots shuffled, as FIFO eviction may leave
    them: live slots need not lead their rows."""
    rng = np.random.default_rng(seed)
    return np.take_along_axis(hist, rng.permuted(
        np.broadcast_to(np.arange(hist.shape[1]), hist.shape), axis=1), axis=1)


@pytest.mark.parametrize(
    "num_users,batch,scatter",
    [(300, 128, False), (2000, 4096, False), (300, 128, True)],
    ids=["doubling", "segmented_scan", "scattered"],
)
def test_packed_step_matches_slots_in_place(num_users, batch, scatter):
    """Under plain SGD, whose update is linear in each gradient, steps on
    the packed live slots match steps over every slot where it stands in
    ``hist``, padding included: a layout that needs no packing.  (Adagrad
    divides by the root of its accumulator, which turns the last-ulp
    differences of a hot row's nearly cancelling gradient sum, added in
    another order, into differences of 1e-5.)"""
    ds, hist, params, state, opt = _svdpp_problem(num_users, 2 * num_users, 32,
                                                  optimizer="sgd")
    if scatter:
        hist = _scatter_rows(hist)
    sizes = np.sum(hist < ds.num_items, axis=1)[ds.user]
    capacity = mf.implicit_capacity(sizes, batch, 32)
    in_place = _steps(params, state, opt, ds, hist, batch, None, weight=scatter,
                      step=_full_width_step)
    packed = _steps(params, state, opt, ds, hist, batch, capacity, weight=scatter)
    _assert_states_close(packed[:2], in_place[:2])
    for got, want in zip(packed[2], in_place[2]):
        assert got.pop("implicit_compact_share") == 1.0
        assert got == pytest.approx(want, rel=1e-5)


def test_packed_step_takes_live_slots_anywhere_in_a_row():
    """Live slots need not lead their rows (FIFO eviction leaves them
    anywhere): the packed step still matches the step packed to all B*H
    slots, with a weighted batch."""
    ds, hist, params, state, opt = _svdpp_problem(300, 600, 32)
    scattered = _scatter_rows(hist)
    assert (scattered[:, 0] == ds.num_items).any()
    assert (np.sort(scattered, axis=1) == hist).all()
    sizes = np.sum(hist < ds.num_items, axis=1)[ds.user]
    capacity = mf.implicit_capacity(sizes, 128, 32)
    full = _steps(params, state, opt, ds, scattered, 128, None, weight=True)
    packed = _steps(params, state, opt, ds, scattered, 128, capacity, weight=True)
    _assert_states_close(packed[:2], full[:2])
    assert [m["implicit_compact_share"] for m in packed[2]] == [1.0] * 3


def test_step_past_its_capacity_trains_on_every_slot():
    """A batch with more live slots than the capacity takes the branch
    packed to all B*H slots: the same tables as the step with no capacity,
    and a step counted 0 in ``implicit_compact_share``."""
    ds, hist, params, state, opt = _svdpp_problem(300, 600, 32)
    capacity = 128 * 32 // 8
    assert all(_live_slots(hist, ds.user[s * 128:(s + 1) * 128], ds.num_items)
               > capacity for s in range(3))
    full = _steps(params, state, opt, ds, hist, 128, None)
    over = _steps(params, state, opt, ds, hist, 128, capacity)
    _assert_states_close(over[:2], full[:2], exact=True)
    assert [m["implicit_compact_share"] for m in over[2]] == [0.0] * 3
    assert [m["abs_err"] for m in over[2]] == [m["abs_err"] for m in full[2]]


def test_implicit_capacity_rule():
    """The smallest B*H / 2^j that holds B * mean + 6 sqrt(B) * std live
    slots: 32,768 of 131,072 at the BookCrossings cell's N(u) (mean 7.37,
    std 2.26, rating-weighted), all of them at full histories."""
    sizes = np.array([7.37 - 2.26, 7.37 + 2.26])
    assert mf.implicit_capacity(sizes, 4096, 32) == 32_768
    assert mf.implicit_capacity(np.full(10, 32), 4096, 32) == 4096 * 32
    # just past half of the slots keeps all of them
    assert mf.implicit_capacity(np.full(10, 17), 4096, 32) == 4096 * 32
    assert mf.implicit_capacity(np.full(10, 16), 4096, 32) == 4096 * 16
