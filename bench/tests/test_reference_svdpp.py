"""The SVD++ reference and its work counts on the CPU, and the trainer
against the reference.

* The reference's per-occurrence gradients, summed per row, are
  ``jax.grad`` of Koren's loss on a small batch with no pruning.
* The work counts (``bench/work_svdpp.py``) match a count by hand and
  never exceed what the reference executes.
* The trainer's SVD++ epochs, driven as the cell drives them, match the
  reference on loss, per-leaf gradient norms and per-leaf change at a
  small size; the old penalty on the whole user vector, histories that
  keep duplicate items, and the trainer in bfloat16 each fail at least one
  tolerance.
"""
import functools
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference_svdpp as ref
from bench import run, work, work_svdpp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "bookx_svdpp_k128.svdpp_epochs"


def _tables(rng, m, n, k):
    f32 = lambda *s: jnp.asarray(rng.normal(0, 0.3, s), jnp.float32)  # noqa: E731
    y = f32(n + 1, k).at[n].set(0.0)
    return f32(m, k), f32(n, k), y, f32(m), f32(n)


def test_gradients_are_jax_grad_of_korens_loss():
    """With thresholds 0 nothing is pruned: the per-occurrence gradients,
    summed per row, are the gradient of
    sum_ratings 0.5 e^2 + 0.5 lam (|p_u|^2 + |q_i|^2 + sum_{j in N(u)} |y_j|^2 + b_u^2 + b_i^2)."""
    rng = np.random.default_rng(0)
    m, n, k, lam, mu = 6, 9, 8, 0.05, 3.0
    user = rng.integers(0, m, 40).astype(np.int32)
    item = rng.integers(0, n, 40).astype(np.int32)
    rating = jnp.asarray(rng.uniform(1, 5, 40), jnp.float32)
    hist = jnp.asarray(ref.histories(user, item, m, n))
    tables = _tables(rng, m, n, k)
    u, i = jnp.asarray(user), jnp.asarray(item)

    def loss(tables):
        p, q, y, b_u, b_i = tables
        h = hist[u]
        z, _, in_set = ref.user_vectors(p[u], y, h)
        e = rating - (mu + b_u[u] + b_i[i] + jnp.sum(z * q[i], axis=1))
        reg = (jnp.sum(p[u] ** 2) + jnp.sum(q[i] ** 2) + jnp.sum(b_u[u] ** 2)
               + jnp.sum(b_i[i] ** 2) + jnp.sum(y[h] ** 2 * in_set[..., None]))
        return 0.5 * jnp.sum(e ** 2) + 0.5 * lam * reg

    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss)(tables)
        _, grads = ref.gradients(tables, u, i, rating, hist, mu, 0.0, 0.0, lam)
    for table, w, (idx, g) in zip(tables, want, grads):
        got = np.zeros(table.shape, np.float64)
        np.add.at(got, np.asarray(idx), np.asarray(g, np.float64))
        # float32 sums of a few dozen terms of order 1
        np.testing.assert_allclose(got, np.asarray(w), rtol=1e-5, atol=1e-5)


def test_work_counts_by_hand():
    """Two users rate item 0 (ranks 2 and 3); user 0 also rates item 1
    (rank 0: pruned away).  N(0) = {0, 1}, N(1) = {0}; k = 4."""
    hist = jnp.asarray([[0, 1], [0, 2]], jnp.int32)          # 2 is padding
    r_user, r_item = jnp.asarray([2, 4]), jnp.asarray([3, 0])
    users, items = jnp.asarray([[0, 1, 0]]), jnp.asarray([[0, 0, 1]])
    flops, nbytes = work_svdpp.step_counts(users, items, hist, r_user, r_item, k=4)
    # pair ranks 2, 3, 0; live history occurrences: 2 of rank 2, 1 of rank 3
    assert int(flops[0]) == 22 * (2 + 3 + 0) + 11 * (2 * 2 + 3)
    # largest pair rank per distinct row: P 2 + 3, Q 3 (item 1 at rank 0),
    # Y item 0 at 3 and item 1 at 2; ids of N(u) for 2 distinct users
    assert int(nbytes[0]) == 16 * ((2 + 3) + 3 + (3 + 2)) + 4 * 3 + 12 * 3


def test_work_counts_within_what_the_reference_executes():
    """The reference runs every factor of every rating and history slot:
    the required counts never exceed that."""
    rng = np.random.default_rng(1)
    m, n, k, steps, batch = 50, 80, 16, 3, 64
    user = rng.integers(0, m, 400)
    item = np.minimum(rng.zipf(1.3, 400) - 1, n - 1)
    hist = jnp.asarray(ref.histories(user, item, m, n))
    slots = hist.shape[1]
    users = jnp.asarray(rng.integers(0, m, (steps, batch)), jnp.int32)
    items = jnp.asarray(rng.integers(0, n, (steps, batch)), jnp.int32)
    r_user = jnp.asarray(rng.integers(0, k + 1, m))
    r_item = jnp.asarray(rng.integers(0, k + 1, n))
    flops, nbytes = work_svdpp.step_counts(users, items, hist, r_user, r_item, k=k)
    executed_flops = batch * k * (work.TRAIN_FLOPS_PER_FACTOR
                                  + slots * work_svdpp.IMPLICIT_FLOPS_PER_FACTOR)
    executed_bytes = (batch * (2 + slots) * k * work_svdpp.ROW_BYTES_PER_FACTOR
                      + batch * (work.TRAIN_BYTES_PER_RATING + slots * work.INDEX_BYTES))
    assert (np.asarray(flops) > 0).all() and (np.asarray(flops) <= executed_flops).all()
    assert (np.asarray(nbytes) > 0).all() and (np.asarray(nbytes) <= executed_bytes).all()


# ---------------------------------------------------------------------------
# The trainer against the reference
# ---------------------------------------------------------------------------

SEED = 2**33 + 21
# relative gaps, per epoch and per leaf, 7 to 80 times the sound
# trainer's reading here (the program and the reference add the same
# float32 terms in another order: duplicate rows summed before they are
# written, not one by one):
# - loss, an epoch's mean |err|: 4e-6;
# - grad, sqrt of an Adagrad table's sum after the first epoch: 1e-6;
# - change, each table's norm of change after three epochs: 1.4e-5.
# The old penalty reads 2e-5, 7e-4 and 3e-3; duplicate histories 1e-2,
# 3.8 and 0.8; bfloat16 1e-3, 0.19 and 0.12.
TOL = {"loss": 1e-4, "grad": 1e-4, "change": 1e-4}


def _cell():
    with open(os.path.join(ROOT, "bench", "configs", "bookx_svdpp_k128.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", "svdpp_epochs.json")) as f:
        traffic = json.load(f)
    # BookCrossings' 8.7 training ratings a user and its item skew, at a
    # thirty-fifth of the users; 1,024 x 32 history slots a step take the
    # long-batch run sums, as the cell's 4,096 x 32 do
    return dict(cfg, num_users=3000, num_items=9700, num_ratings=32850, k=16,
                batch_size=1024), traffic


def _worst_gaps():
    """The largest relative gap of each kind between the trainer's readings
    and the reference's."""
    cfg, traffic = _cell()
    driver = run.load_driver(traffic["kind"])
    trainer, prog, train, s_train = driver.setup(cfg, traffic, SEED)
    del trainer
    want = ref.train_readings(cfg, train, s_train, steps=traffic["compare_epochs"])

    def rel(a, b):
        return abs(a - b) / abs(b)

    return {
        "loss": max(rel(a, b) for a, b in zip(prog["loss"], want["loss"])),
        "grad": max(rel(prog["grad"][x], want["grad"][x]) for x in ref.LEAVES),
        "change": max(rel(prog["change"][x], want["change"][x]) for x in ref.LEAVES),
    }


def test_trainer_matches_the_reference():
    gaps = _worst_gaps()
    assert all(gaps[key] <= TOL[key] for key in TOL), gaps


def _old_penalty(monkeypatch):
    from repro.core import mf

    src = inspect.getsource(mf._train_step)
    assert "lam * params.p[u]" in src
    namespace = dict(vars(mf))
    exec(compile(src.replace("lam * params.p[u]", "lam * pu"), mf.__file__, "exec"),
         namespace)
    monkeypatch.setattr(mf, "_train_step", namespace["_train_step"])
    mf.train_epoch_scan.clear_cache()


def _duplicate_histories(monkeypatch):
    from repro.core import trainer

    def with_duplicates(ds, max_hist):
        hist = np.full((ds.num_users, max_hist), ds.num_items, np.int32)
        counts = np.zeros(ds.num_users, np.int32)
        for u, i in zip(np.asarray(ds.user), np.asarray(ds.item)):
            if counts[u] < max_hist:
                hist[u, counts[u]] = i
                counts[u] += 1
        return hist, 0

    monkeypatch.setattr(trainer, "build_user_history", with_duplicates)


def _bfloat16(monkeypatch):
    from repro.core import mf

    monkeypatch.setattr(mf, "init_params",
                        functools.partial(mf.init_params, dtype=jnp.bfloat16))


@pytest.mark.parametrize("plant", [_old_penalty, _duplicate_histories, _bfloat16])
def test_planted_variants_fail_a_tolerance(plant, monkeypatch):
    from repro.core import mf

    plant(monkeypatch)
    try:
        gaps = _worst_gaps()
    finally:
        mf.train_epoch_scan.clear_cache()
    assert any(gaps[key] > TOL[key] for key in TOL), gaps


# ---------------------------------------------------------------------------
# The cell's ``correct`` at that small size
# ---------------------------------------------------------------------------


def _correct():
    import time

    from bench.harness import Run

    cfg, traffic = _cell()
    with open(os.path.join(ROOT, "bench", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    driver = run.load_driver(traffic["kind"])
    driver.check(cfg, traffic)
    out = driver.drive(Run(time.perf_counter(), peak, 1, None), cfg, traffic, SEED, 0.5)
    result = out.checks(limits)
    return all(c["value"] <= c["limit"] for c in result.values()), result


def test_sound_cell_is_correct():
    ok, result = _correct()
    assert ok, result


def _unchanged(orig):
    def step(params, opt_state, batches, *args, **kwargs):
        copy = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        _, _, metrics = orig(*copy, batches, *args, **kwargs)
        return params, opt_state, metrics
    return step


def _half_batch(orig):
    def step(params, opt_state, batches, *args, **kwargs):
        half = {k: v[:, : v.shape[1] // 2] for k, v in batches.items()}
        return orig(params, opt_state, half, *args, **kwargs)
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_cell_faults_are_not_correct(fault, monkeypatch):
    from repro.core import mf

    monkeypatch.setattr(mf, "train_epoch_scan", fault(mf.train_epoch_scan))
    ok, result = _correct()
    assert not ok, result


def test_implicit_side_reads_only_the_implicit_arrays():
    """On op texts as the TPU trace names them: the table, the slots and
    the run sums' upper levels count; the batch's own arrays, the eval
    batch and the scan's loop do not."""
    import types

    reader = run.load_reader("implicit_side_us.svdpp")
    ops = {
        "%write_rows.20 = (f32[340555,128]{1,0:T(8,128)}) custom-call(s32[131072]{0})": 3.0,
        "%fusion.159 = f32[131072,512]{1,0:T(8,128)} fusion(f32[131072,512]{1,0})": 2.0,
        "%pad.656 = f32[32768,256]{1,0:T(8,128)} pad(f32[16384,256]{1,0})": 1.0,
        "%fusion.155 = pred[4096,32]{1,0:T(8,128)} fusion(f32[4096,32]{1,0})": 0.5,
        "%write_rows.19 = (f32[340554,128]{1,0:T(8,128)}) custom-call(s32[4096]{0})": 7.0,
        "%reduce_sum.30 = f32[8192,128]{1,0} reduce(f32[8192,32,128]{2,1,0})": 11.0,
        "%while.104 = (s32[], f32[340555,128]{1,0:T(8,128)})": 13.0,
    }
    trace = {"ops": {name: [1, sec] for name, sec in ops.items()}}
    ctx = {"svdpp": {"implicit_table": "f32[340555,128]", "batch": 4096,
                     "max_hist": 32}, "train": {"steps": 2}}
    assert reader(types.SimpleNamespace(trace=trace, ctx=ctx)) == 1e6 * 6.5 / 2
    assert reader(types.SimpleNamespace(trace=None, ctx={})) is None
