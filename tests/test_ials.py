"""Implicit ALS (``core/als.py``, ``data/pairs.py``) on the CPU at a small
size, against the plain reference ``bench/reference_ials.py`` and against
systems built by hand in float64.

The log: 300 users x 120 items, 6,000 ratings with many duplicates (a
Zipf item head), item 119's ratings removed so that one item has no pair.
The layouts use chunks of 16 pairs, solve blocks of 64 rows and launches of
8 chunks, so that the hot items span many chunks and launches and the last
user block is pulled back over the one before it.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import als
from repro.core.ranks import effective_ranks, mask_rows
from repro.core.threshold import thresholds_from_matrices
from repro.core.trainer import DPMFTrainer, TrainConfig
from repro.data import pairs as pairs_lib
from repro.data import synthetic_ratings
from repro.data.ratings import RatingsDataset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import reference_ials  # noqa: E402

M, N, K = 300, 120, 16
ALPHA, LAM = 40.0, 0.1
SIZES = dict(chunk=16, rows_per_block=64, chunks_per_launch=8)
# Both sides solve the same float32 systems, summed in another order and
# factorised another way (Cholesky against LU); their condition numbers
# here stay below 1e3 (``test_half_step_matches_the_reference`` checks),
# so rows agree to about cond x 2^-24 x k ~ 1e-5, and a tenth of that
# headroom again is left for the summation order.  The reference's
# Gramians in one bfloat16 pass miss it by two orders of magnitude.
ROW_TOL = 1e-4


@pytest.fixture(scope="module")
def log():
    ds = synthetic_ratings(M, N, 6000, seed=7)
    keep = ds.item != N - 1
    return RatingsDataset(ds.user[keep], ds.item[keep], ds.rating[keep],
                          num_users=M, num_items=N)


@pytest.fixture(scope="module")
def layouts(log):
    return pairs_lib.pair_layouts(log.user, log.item, log.rating, M, N,
                                  alpha=ALPHA, **SIZES)


@pytest.fixture(scope="module")
def sides(log):
    cfg = {"num_users": M, "num_items": N, "implicit_alpha": ALPHA}
    return reference_ials.sides(cfg, (log.user, log.item, log.rating))


def _tables(seed=0, scale=0.1):
    kp, kq = jax.random.split(jax.random.PRNGKey(seed))
    return (scale * jax.random.normal(kp, (M, K)), scale * jax.random.normal(kq, (N, K)))


def _pairs(log):
    """The merged pairs as a dense (M, N) matrix of summed ratings."""
    r = np.zeros((M, N))
    np.add.at(r, (log.user, log.item), log.rating.astype(np.float64))
    return r


def test_layout_merges_duplicates_and_spans_chunks(log, layouts):
    r = _pairs(log)
    assert layouts.pairs == int(np.sum(r > 0)) < len(log)   # duplicates merged
    item = layouts.item
    counts = np.sum(r > 0, axis=0)
    assert np.array_equal(np.asarray(item.count), counts)
    assert counts[N - 1] == 0 and counts.max() > 3 * SIZES["chunk"]
    assert item.split_rows == int(np.sum(counts > SIZES["chunk"]))
    # every pair sits in one chunk of its row, with its confidence
    rows = np.asarray(item.row)[:item.chunks]
    conf = np.asarray(item.conf)[:item.chunks]
    col = np.asarray(item.col)[:item.chunks]
    live = conf > 0
    got = np.zeros((N, M))
    got[np.broadcast_to(rows[:, None], col.shape)[live], col[live]] = conf[live]
    np.testing.assert_allclose(got, np.where(r.T > 0, 1.0 + ALPHA * r.T, 0.0), rtol=1e-6)
    assert item.chunks == int(np.sum(-(-counts // SIZES["chunk"])))
    assert layouts.pad_share == pytest.approx(
        1 - 2 * layouts.pairs / ((layouts.user.chunks + item.chunks) * SIZES["chunk"]))


@pytest.mark.parametrize("rate", [0.0, 0.3])
def test_half_step_matches_the_reference(layouts, sides, rate):
    """Both half-steps' rows against the reference's solves, at rate 0
    and at the thresholds of rate 0.3."""
    p, q = _tables()
    p = p * 30.0   # the scale of a solved user table, so that ranks vary
    t_p, t_q = thresholds_from_matrices(p, q, rate)
    users, items = sides
    got_p, _ = als.half_step(jnp.copy(p), q, t_p, t_q, layouts.user, lam=LAM)
    with jax.default_matmul_precision("highest"):
        want_p = reference_ials.half_step(p, q, t_p, t_q, users, LAM)
        bf16_p = reference_ials.half_step(p, q, t_p, t_q, users, LAM, gram="bf16")
    assert reference_ials.row_gap(got_p, want_p) < ROW_TOL
    assert reference_ials.row_gap(bf16_p, want_p) > 10 * ROW_TOL
    got_q, _ = als.half_step(jnp.copy(q), want_p, t_q, t_p, layouts.item, lam=LAM)
    with jax.default_matmul_precision("highest"):
        want_q = reference_ials.half_step(q, want_p, t_q, t_p, items, LAM)
    assert reference_ials.row_gap(got_q, want_q) < ROW_TOL
    # the systems are well enough conditioned for ROW_TOL to hold
    f = np.asarray(mask_rows(q, t_q), np.float64)
    a = f.T @ f + LAM * np.max(np.asarray(layouts.user.count)) * np.eye(K)
    assert np.linalg.cond(a) < 1e3


def _system(r, fixed, t_f, u):
    """Row ``u``'s system in float64 from the dense pairs ``r``:
    Y~'C_u Y~ + lam n_u I and Y~'C_u p(u)."""
    f = np.asarray(mask_rows(fixed, t_f), np.float64)
    c = np.where(r[u] > 0, 1.0 + ALPHA * r[u], 1.0)
    n = np.sum(r[u] > 0)
    return f.T @ (c[:, None] * f) + LAM * n * np.eye(K), f.T @ (c * (r[u] > 0))


def test_pruned_rows_hold_their_tail_and_solve_the_leading_block(log, layouts):
    """At rate 0.3 a row's factors from its rank on come back bitwise, and
    its first r solve the leading r x r block of its system."""
    p, q = _tables(1)
    p = p * 30.0
    t_p, t_q = thresholds_from_matrices(p, q, 0.3)
    got, rank_sum = als.half_step(jnp.copy(p), q, t_p, t_q, layouts.user, lam=LAM)
    got, old = np.asarray(got), np.asarray(p)
    r_u = np.asarray(effective_ranks(p, t_p))
    assert r_u.max() < K and len(set(r_u.tolist())) > 4   # the ranks vary
    assert int(rank_sum) == int(np.sum(r_u))
    r = _pairs(log)
    for u in range(M):
        ru = r_u[u]
        assert got[u, ru:].tobytes() == old[u, ru:].tobytes()
        if ru == 0:
            continue
        a, b = _system(r, q, t_q, u)
        want = np.linalg.solve(a[:ru, :ru], b[:ru])
        np.testing.assert_allclose(got[u, :ru], want, rtol=ROW_TOL,
                                   atol=ROW_TOL * np.abs(want).max())


def test_dense_objective_never_rises(layouts):
    """At rate 0 each half-step minimises the loss over its side exactly, so
    the loss over all pairs does not rise over 5 epochs, half-step by
    half-step.  Slack: the loss is a float32 sum of ~1e4 terms whose
    rounding moves it by ~1e-7 of its size."""
    p, q = _tables(2)
    zero = jnp.float32(0.0)
    losses = [float(als.objective(p, q, zero, zero, layouts, lam=LAM))]
    for _ in range(5):
        p, _ = als.half_step(p, q, zero, zero, layouts.user, lam=LAM)
        losses.append(float(als.objective(p, q, zero, zero, layouts, lam=LAM)))
        q, _ = als.half_step(q, p, zero, zero, layouts.item, lam=LAM)
        losses.append(float(als.objective(p, q, zero, zero, layouts, lam=LAM)))
    for before, after in zip(losses, losses[1:]):
        assert after <= before * (1 + 1e-6), losses
    assert losses[-1] < 0.5 * losses[0]


def test_objective_is_the_brute_force_loss(log, layouts):
    """The Gramian trick against the sum over every user x item pair."""
    p, q = _tables(3)
    p = p * 30.0
    t_p, t_q = thresholds_from_matrices(p, q, 0.3)
    r = _pairs(log)
    s = np.asarray(mask_rows(p, t_p), np.float64) @ np.asarray(mask_rows(q, t_q), np.float64).T
    c = np.where(r > 0, 1.0 + ALPHA * r, 1.0)
    n_u, n_i = np.sum(r > 0, axis=1), np.sum(r > 0, axis=0)
    pen = LAM * (n_u @ np.sum(np.asarray(p, np.float64) ** 2, 1)
                 + n_i @ np.sum(np.asarray(q, np.float64) ** 2, 1))
    want = np.sum(c * ((r > 0) - s) ** 2) + pen
    got = float(als.objective(p, q, t_p, t_q, layouts, lam=LAM))
    assert got == pytest.approx(want, rel=1e-5)


def test_hot_row_gramian_chunked_and_whole_agree(log, layouts):
    """The most popular item's sums over its 19 chunks of 16 pairs (three
    launches of 8) against the same row laid out in one chunk."""
    whole = pairs_lib.pair_layouts(log.user, log.item, log.rating, M, N, alpha=ALPHA,
                                   chunk=512, rows_per_block=64, chunks_per_launch=1)
    hot = int(np.argmax(np.asarray(layouts.item.count)))
    assert np.asarray(layouts.item.count)[hot] > 2 * 8 * SIZES["chunk"]
    p, _ = _tables(4)
    masked, _ = als.als_gram_fixed(p, jnp.float32(0.0))
    sums = []
    for side in (layouts.item, whole.item):
        blk = next(b for b in side.blocks if b.lo + b.first <= hot < b.lo + side.rows_per_block)
        a, b = als.als_gram(masked, side.row, side.col, side.conf, blk.start, blk.launches,
                            blk.lo, rows=side.rows_per_block, launch=side.chunks_per_launch)
        sums.append((np.asarray(a)[hot - blk.lo], np.asarray(b)[hot - blk.lo]))
    (a1, b1), (a2, b2) = sums
    assert layouts.item.blocks[0].launches >= 3
    np.testing.assert_allclose(a1, a2, rtol=1e-5, atol=1e-5 * np.abs(a2).max())
    np.testing.assert_allclose(b1, b2, rtol=1e-5, atol=1e-5 * np.abs(b2).max())


def test_rows_without_pairs_keep_their_factors(log, layouts):
    p, q = _tables(5)
    zero = jnp.float32(0.0)
    got, _ = als.half_step(jnp.copy(q), p, zero, zero, layouts.item, lam=LAM)
    assert np.asarray(got)[N - 1].tobytes() == np.asarray(q)[N - 1].tobytes()
    assert not np.array_equal(np.asarray(got)[0], np.asarray(q)[0])


def test_trainer_runs_ials_through_the_normal_path(log):
    """Dense epoch, calibration, Algorithm 1, pruned epochs and the
    evaluation, with no optimizer state; the epochs' losses are the
    reference's."""
    test = RatingsDataset(log.user[:500], log.item[:500], log.rating[:500],
                          num_users=M, num_items=N)
    config = TrainConfig(k=K, epochs=3, pruning_rate=0.3, objective="ials",
                         lam=LAM, implicit_alpha=ALPHA, seed=11)
    trainer = DPMFTrainer(config, log, test)
    assert trainer.opt_state is None
    records = trainer.run()
    assert [r.epoch for r in records] == [0, 1, 2]
    assert records[0].work_fraction == 1.0 and records[1].work_fraction < 1.0
    assert trainer.perm is not None and float(trainer.t_p) > 0
    assert all(np.isfinite(r.test_mae) for r in records)
    cfg = {"num_users": M, "num_items": N, "implicit_alpha": ALPHA, "k": K, "lam": LAM,
           "pruning_rate": 0.3, "init_scale": 0.1}
    ref = reference_ials.train_readings(cfg, (log.user, log.item, log.rating), 11, epochs=3)
    assert np.array_equal(np.asarray(trainer.perm), ref["perm"])
    assert reference_ials.loss_gap([r.train_abs_err for r in records], ref["loss"]) < 1e-4


@pytest.mark.parametrize("kw", [{"variant": "bias"}, {"strategy": "twin"}])
def test_trainer_refuses_what_ials_does_not_solve(log, kw):
    with pytest.raises(ValueError, match="ials"):
        DPMFTrainer(TrainConfig(k=K, objective="ials", **kw), log)
