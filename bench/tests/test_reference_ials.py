"""The implicit-ALS reference, its work counts and its readers on the CPU,
and the cell's comparison at a small size.

* The reference's systems (the Gramian trick: Y~'Y~ plus the pairs' sums)
  and its loss equal a brute-force version that builds the dense C and P
  matrices over every user x item pair.
* The work counts (``bench/work_ials.py``) match a count by hand.
* The readers take the Gramian and solve programs' times by name.
* At a small size the cell's driver reads the sound program as correct,
  and the lower-precision control and the planted fault that leaves out
  half of each row's pairs fail its limits.
"""
import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference_ials as ref
from bench import run, work, work_ials
from bench.harness import Run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "ml25m_ials_k128.ials_epochs"
M, N, K, ALPHA, LAM = 7, 9, 5, 40.0, 0.1


def _log(rng):
    user = rng.integers(0, M, 40).astype(np.int32)
    item = rng.integers(0, N - 1, 40).astype(np.int32)   # item N-1 has no pair
    rating = rng.uniform(0.5, 5.0, 40).astype(np.float32)
    return user, item, rating


def _dense(user, item, rating):
    r = np.zeros((M, N))
    np.add.at(r, (user, item), rating.astype(np.float64))
    return (r > 0).astype(np.float64), np.where(r > 0, 1.0 + ALPHA * r, 1.0)


def test_systems_and_loss_are_the_brute_force_ones():
    rng = np.random.default_rng(0)
    user, item, rating = _log(rng)
    p, c = _dense(user, item, rating)
    cfg = {"num_users": M, "num_items": N, "implicit_alpha": ALPHA}
    users, items = ref.sides(cfg, (user, item, rating))
    x = jnp.asarray(rng.normal(0, 1.0, (M, K)), jnp.float32)
    y = jnp.asarray(rng.normal(0, 1.0, (N, K)), jnp.float32)
    t_x, t_y = 0.3, 0.2
    xm = np.asarray(ref.ranks(x, t_x)[:, None] > np.arange(K), np.float64) * np.asarray(x)
    ym = np.asarray(ref.ranks(y, t_y)[:, None] > np.arange(K), np.float64) * np.asarray(y)
    with jax.default_matmul_precision("highest"):
        masked, g = ref._fixed_gram(y, t_y, gram="highest")
        a, b = ref.systems(masked, users, 0, M)
    for u in range(M):
        want_a = ym.T @ (c[u][:, None] * ym)          # Y~'C_u Y~
        want_b = ym.T @ (c[u] * p[u])                 # Y~'C_u p(u)
        np.testing.assert_allclose(np.asarray(g) + np.asarray(a[u]), want_a, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(b[u]), want_b, rtol=1e-5, atol=1e-4)
    s = xm @ ym.T
    n_u, n_i = p.sum(1), p.sum(0)
    want = (np.sum(c * (p - s) ** 2)
            + LAM * (n_u @ np.sum(np.asarray(x, np.float64) ** 2, 1)
                     + n_i @ np.sum(np.asarray(y, np.float64) ** 2, 1)))
    with jax.default_matmul_precision("highest"):
        got = ref.objective(x, y, t_x, t_y, users, items, LAM)
    assert got == pytest.approx(want, rel=1e-5)
    # the half-step solves the brute-force system; a row without pairs
    # keeps its factors
    with jax.default_matmul_precision("highest"):
        new_y = ref.half_step(y, x, t_y, t_x, items, LAM)
    assert np.asarray(new_y)[N - 1].tobytes() == np.asarray(y)[N - 1].tobytes()
    i = int(np.argmax(n_i))
    r_i = int(ref.ranks(y, t_y)[i])
    a_i = xm.T @ (c[:, i][:, None] * xm) + LAM * n_i[i] * np.eye(K)
    b_i = xm.T @ (c[:, i] * p[:, i])
    want_i = np.linalg.solve(a_i[:r_i, :r_i], b_i[:r_i])
    np.testing.assert_allclose(np.asarray(new_y)[i, :r_i], want_i, rtol=1e-4)
    assert np.asarray(new_y)[i, r_i:].tobytes() == np.asarray(y)[i, r_i:].tobytes()


def test_work_counts_by_hand():
    user = np.array([0, 0, 1, 2], np.int32)
    item = np.array([0, 1, 1, 2], np.int32)
    r_user, r_item = np.array([3, 1, 2, 4]), np.array([2, 3, 0])
    got = work_ials.epoch_counts(user, item, r_user, r_item)
    pair = np.array([2, 3, 1, 0])
    assert got["gram_flops"] == 4 * np.sum(pair ** 2) + 2 * (4 + 9 + 0) + 2 * (9 + 1 + 4 + 16)
    assert got["gram_bytes"] == 2 * (4 * pair.sum() + 8 * 4) + 4 * (5 + 10)
    # users 0, 1, 2 and items 0, 1, 2 have pairs; user 3 has none
    assert got["solve_flops"] == pytest.approx((27 + 1 + 8 + 8 + 27 + 0) / 3)


def test_readers_take_the_programs_by_name():
    trace = {"modules": {"jit_als_gram": [10, 0.6], "jit_als_gram_fixed": [2, 0.2],
                         "jit_als_solve": [10, 0.4], "jit_als_objective": [2, 0.1]},
             "window_s": 2.0, "busy_s": 1.5}
    ctx = {"ials": {"epochs": 2, "gram_flops": 1e12, "gram_bytes": 8e9, "solve_flops": 3e11},
           "train": {"epochs": 2}}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = types.SimpleNamespace(trace=trace, ctx=ctx, peak=peak)
    assert run.load_reader("als_gram_ms.ials")(r) == pytest.approx(1e3 * 0.8 / 2)
    assert run.load_reader("als_solve_ms.ials")(r) == pytest.approx(1e3 * 0.4 / 2)
    least = work.roofline_s(1e12, 8e9, peak)
    assert run.load_reader("als_gram_roofline.ials")(r) == pytest.approx(100 * 2 * least / 0.8)
    assert run.load_reader("train_mfu.ials")(r) == pytest.approx(
        100 * 2 * 1.3e12 / 2.0 / 197e12)
    assert run.load_reader("train_idle_share.ials")(r) == pytest.approx(25.0)
    empty = types.SimpleNamespace(trace=None, ctx={}, peak=peak)
    for name in ("als_gram_ms.ials", "als_solve_ms.ials", "als_gram_roofline.ials",
                 "train_mfu.ials", "train_idle_share.ials"):
        assert run.load_reader(name)(empty) is None


def _cell():
    with open(os.path.join(ROOT, "bench", "configs", "ml25m_ials_k128.json")) as f:
        cfg = dict(json.load(f), num_users=300, num_items=120, num_ratings=25000, k=16)
    with open(os.path.join(ROOT, "bench", "traffic", "ials_epochs.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "bench", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    return cfg, traffic, limits


def _correct(readings, limits):
    return all(readings[name] <= limit for name, limit in limits.items())


def test_sound_cell_is_correct_and_control_and_fault_are_not():
    cfg, traffic, limits = _cell()
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    driver = run.load_driver(traffic["kind"])
    driver.check(cfg, traffic)
    seed = 2**33 + 21
    out = driver.drive(Run(time.perf_counter(), peak, 1, None), cfg, traffic, seed, 0.2)
    assert _correct(out.readings, limits), out.readings
    controls = driver.controls(cfg, traffic, seed)
    assert not _correct(controls["control_bf16"], limits), controls
    assert not _correct(controls["fault_half"], limits), controls


def test_check_refuses_a_program_without_the_objective(monkeypatch):
    """A program whose trainer raises ``unknown objective`` for
    ``objective="ials"`` (the parent of this cell) is refused by the cell's
    check, before any device work."""
    from repro.core import trainer

    cfg, traffic, _ = _cell()
    driver = run.load_driver(traffic["kind"])
    driver.check(cfg, traffic)
    real = trainer.DPMFTrainer.__init__

    def parent(self, config, *args, **kwargs):
        if config.objective == "ials":
            raise ValueError(f"unknown objective {config.objective!r}")
        real(self, config, *args, **kwargs)

    monkeypatch.setattr(trainer.DPMFTrainer, "__init__", parent)
    with pytest.raises(run.Refused, match="objective='ials'"):
        driver.check(cfg, traffic)
