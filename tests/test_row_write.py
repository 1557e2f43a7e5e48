"""The combined row write of the additive row optimizers (SGD, Adagrad):
each touched row written once, duplicates summed on the device.

Checked against a sequential NumPy reference that applies one occurrence
at a time, as a serial scatter-add does, on three id patterns: all
distinct, all on one row, and Zipf-like with about a quarter of the batch
on one row.  The kernel runs in interpret mode here; its compile for the
chip is in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mf
from repro.kernels.row_write import LANES, write_rows
from repro.optim.optimizers import DOUBLING_MAX, RowOptimizer, rows_written

ROWS, K, B = 300, 16, 256
LR, EPS = 0.05, 1e-8


def _ids(pattern: str, rng) -> np.ndarray:
    if pattern == "distinct":
        return rng.permutation(ROWS)[:B]
    if pattern == "one_row":
        return np.full(B, 17)
    # Zipf-like: a quarter of the batch on row 3, the rest skewed over all rows
    ids = np.minimum(rng.zipf(1.3, B) - 1, ROWS - 1)
    ids[rng.permutation(B)[: B // 4]] = 3
    return ids


def _reference(name, param, acc, idx, grad, mask):
    """Sequential per-occurrence update: every delta reads the accumulator
    as it was before the batch, then every occurrence adds in turn."""
    param = param.astype(np.float64)
    acc = acc.astype(np.float64)
    g = grad.astype(np.float64) * mask
    if name == "sgd":
        delta = -LR * g
    else:
        delta = -LR * g / np.sqrt(acc[idx] + g * g + EPS) * mask
    for b in range(idx.shape[0]):
        param[idx[b]] += delta[b]
        acc[idx[b]] += g[b] * g[b]
    return param, acc


def _inputs(pattern: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    param = rng.normal(0, 0.1, (ROWS, K)).astype(np.float32)
    acc = rng.uniform(0, 0.5, (ROWS, K)).astype(np.float32)
    idx = _ids(pattern, rng).astype(np.int32)
    grad = rng.normal(0, 1.0, (B, K)).astype(np.float32)
    mask = (rng.uniform(size=(B, K)) < 0.7).astype(np.float32)
    return param, acc, idx, grad, mask


def _apply(name, param, acc, idx, grad, mask):
    opt = RowOptimizer(name=name, eps=EPS)
    state = {} if name == "sgd" else {"acc": jnp.asarray(acc)}
    new_p, new_s = opt.apply_rows(
        jnp.asarray(param), state, jnp.asarray(idx), jnp.asarray(grad),
        jnp.asarray(mask), LR,
    )
    return np.asarray(new_p), (np.asarray(new_s["acc"]) if new_s else None)


@pytest.mark.parametrize("pattern", ["distinct", "one_row", "zipf"])
@pytest.mark.parametrize("name", ["sgd", "adagrad"])
def test_combined_write_matches_sequential_adds(name, pattern):
    param, acc, idx, grad, mask = _inputs(pattern)
    got_p, got_acc = _apply(name, param, acc, idx, grad, mask)
    want_p, want_acc = _reference(name, param, acc, idx, grad, mask)
    # float32 sums of up to B terms, in another order than the reference's
    np.testing.assert_allclose(got_p, want_p, rtol=1e-5, atol=1e-5)
    if name == "adagrad":
        np.testing.assert_allclose(got_acc, want_acc, rtol=1e-5, atol=1e-4)
    untouched = np.setdiff1d(np.arange(ROWS), idx)
    np.testing.assert_array_equal(got_p[untouched], param[untouched])


@pytest.mark.parametrize("name", ["sgd", "adagrad"])
def test_long_batch_write_matches_sequential_adds(name):
    """Past ``DOUBLING_MAX`` occurrences (SVD++'s history slots) the run
    sums take the segmented scan: the same sums in another float32 order,
    a quarter of the batch on one row."""
    rng = np.random.default_rng(5)
    size = DOUBLING_MAX + 1000
    param = rng.normal(0, 0.1, (ROWS, K)).astype(np.float32)
    acc = rng.uniform(0, 0.5, (ROWS, K)).astype(np.float32)
    idx = np.minimum(rng.zipf(1.3, size) - 1, ROWS - 1).astype(np.int32)
    idx[rng.permutation(size)[: size // 4]] = 3
    grad = rng.normal(0, 1.0, (size, K)).astype(np.float32)
    mask = (rng.uniform(size=(size, K)) < 0.7).astype(np.float32)
    got_p, got_acc = _apply(name, param, acc, idx, grad, mask)
    want_p, want_acc = _reference(name, param, acc, idx, grad, mask)
    # float32 sums of up to ~4,500 terms of order 0.05 (deltas) or 1 (g^2)
    np.testing.assert_allclose(got_p, want_p, rtol=1e-4, atol=1e-4)
    if name == "adagrad":
        np.testing.assert_allclose(got_acc, want_acc, rtol=1e-4, atol=1e-3)
    untouched = np.setdiff1d(np.arange(ROWS), idx)
    np.testing.assert_array_equal(got_p[untouched], param[untouched])


@pytest.mark.parametrize("pattern", ["distinct", "one_row", "zipf"])
@pytest.mark.parametrize("name", ["sgd", "adagrad"])
def test_weight_zero_rows_are_bitwise_inert(name, pattern):
    """Occurrences with an all-zero mask, spliced in anywhere and on rows
    nothing else touches, change no bit of any table."""
    param, acc, idx, grad, mask = _inputs(pattern, seed=1)
    rng = np.random.default_rng(2)
    n_dead = 64
    at = np.sort(rng.choice(B + n_dead, n_dead, replace=False))
    keep = np.setdiff1d(np.arange(B + n_dead), at)
    fresh = np.setdiff1d(np.arange(ROWS), idx)[:n_dead // 2]
    dead_ids = np.concatenate([fresh, rng.choice(idx, n_dead - fresh.size)])

    idx2 = np.empty(B + n_dead, np.int32)
    idx2[keep], idx2[at] = idx, dead_ids
    grad2 = rng.normal(0, 1.0, (B + n_dead, K)).astype(np.float32)
    grad2[keep] = grad
    mask2 = np.zeros((B + n_dead, K), np.float32)
    mask2[keep] = mask

    base_p, base_acc = _apply(name, param, acc, idx, grad, mask)
    got_p, got_acc = _apply(name, param, acc, idx2, grad2, mask2)
    np.testing.assert_array_equal(got_p, base_p)
    np.testing.assert_array_equal(got_p[fresh], param[fresh])
    if name == "adagrad":
        np.testing.assert_array_equal(got_acc, base_acc)
        np.testing.assert_array_equal(got_acc[fresh], acc[fresh])


@pytest.mark.parametrize("pattern", ["distinct", "one_row", "zipf"])
def test_rows_written_counts_distinct_live_ids(pattern):
    _, _, idx, _, mask = _inputs(pattern)
    mask[::5] = 0.0   # every fifth occurrence adds nothing
    live = mask.any(axis=1)
    got = int(rows_written(jnp.asarray(idx), jnp.asarray(live), ROWS))
    assert got == np.unique(idx[live]).size


def _write_only_listed_rows(widths, size, interpret):
    rng = np.random.default_rng(3)
    rows = np.full(size, ROWS + 9, np.int32)            # past the table: skipped
    chosen = rng.choice(ROWS, 100, replace=False)
    rows[rng.choice(size, 100, replace=False)] = chosen
    values = [rng.normal(size=(size, w)).astype(np.float32) for w in widths]
    tables = [rng.normal(size=(ROWS, w)).astype(np.float32) for w in widths]
    want = [t.copy() for t in tables]
    for j in np.flatnonzero(rows < ROWS):
        for w, v in zip(want, values):
            w[rows[j]] = v[j]
    got = write_rows(
        jnp.asarray(rows), tuple(map(jnp.asarray, values)),
        tuple(map(jnp.asarray, tables)), interpret=interpret,
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("num_tables,size", [(1, B), (2, B), (2, B - 3)])
def test_write_rows_kernel_writes_only_listed_rows(num_tables, size):
    """The row-DMA kernel (interpreted here), which takes tables whose
    rows are whole 128-lane tiles."""
    _write_only_listed_rows([LANES] * num_tables, size, interpret=True)


@pytest.mark.parametrize("widths", [(1,), (1, 1), (LANES, 1), (16,)],
                         ids=["bias", "bias_and_acc", "factor_and_bias", "k16"])
def test_write_rows_narrow_tables_write_only_listed_rows(widths):
    """A table narrower than a lane tile (a bias column) is written by
    XLA's scatter of the same rows, beside the kernel's wide tables: with
    ``interpret=False`` on the CPU only the narrow tables can be written
    without the kernel."""
    narrow_only = all(w % LANES for w in widths)
    _write_only_listed_rows(widths, B - 3, interpret=not narrow_only)


def test_train_step_reports_shares_of_rows_written():
    """The step's counters are the distinct ids of its live occurrences
    over the batch size, user side and item side."""
    rng = np.random.default_rng(4)
    params = mf.init_params(jax.random.PRNGKey(0), ROWS, ROWS, K)
    opt = RowOptimizer(name="adagrad")
    users = rng.integers(0, ROWS, B).astype(np.int32)
    items = _ids("zipf", rng).astype(np.int32)
    weight = (rng.uniform(size=B) < 0.8).astype(np.float32)
    _, _, metrics = mf.train_step(
        params, mf.init_opt_state(params, opt),
        {"user": jnp.asarray(users), "item": jnp.asarray(items),
         "rating": jnp.asarray(rng.uniform(1, 5, B), jnp.float32),
         "weight": jnp.asarray(weight)},
        jnp.float32(0.0), jnp.float32(0.0), jnp.float32(LR),
        jnp.ones((K,), jnp.float32), opt=opt, lam=0.02,
    )
    live = weight != 0
    assert float(metrics["user_rows_share"]) == np.unique(users[live]).size / B
    assert float(metrics["item_rows_share"]) == np.unique(items[live]).size / B
