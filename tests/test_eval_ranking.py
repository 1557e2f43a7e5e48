"""Ranking metrics: numpy-oracle pinning (ties, topk == n, partial
holdouts), exact engine/oracle parity at threshold 0 on every serving path
(streaming, kernel, sharded), and the one-scan epoch variant."""
import math
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import mf
from repro.core.trainer import DPMFTrainer, TrainConfig
from repro.data import synthetic_ratings, train_test_split
from repro.eval import ranking as R
from repro.launch.mesh import make_mesh
from repro.serving import ServingEngine


# ---------------------------------------------------------------------------
# numpy brute-force metric oracle
# ---------------------------------------------------------------------------


def np_ranking_metrics(topk_idx, relevant_sets):
    """Scalar-loop HR/NDCG/recall reference (the module's definitions)."""
    hr, ndcg, recall = [], [], []
    for ids, rel in zip(topk_idx, relevant_sets):
        rel = set(int(x) for x in rel)
        if not rel:
            continue
        hits = [1.0 if int(i) in rel else 0.0 for i in ids]
        dcg = sum(h / math.log2(j + 2) for j, h in enumerate(hits))
        idcg = sum(
            1.0 / math.log2(j + 2) for j in range(min(len(ids), len(rel)))
        )
        hr.append(1.0 if any(hits) else 0.0)
        ndcg.append(dcg / idcg)
        recall.append(sum(hits) / len(rel))
    n = max(len(hr), 1)
    return sum(hr) / n, sum(ndcg) / n, sum(recall) / n, len(hr)


def _as_padded(relevant_sets):
    width = max((len(r) for r in relevant_sets), default=1)
    width = max(width, 1)
    rel = np.full((len(relevant_sets), width), R.PAD_ITEM, np.int32)
    counts = np.zeros(len(relevant_sets), np.int32)
    for row, items in enumerate(relevant_sets):
        rel[row, : len(items)] = sorted(items)
        counts[row] = len(items)
    return rel, counts


def test_ranking_counts_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    b, k, n_items = 64, 10, 200
    topk_idx = np.stack(
        [rng.choice(n_items, k, replace=False) for _ in range(b)]
    ).astype(np.int32)
    relevant_sets = [
        list(rng.choice(n_items, rng.integers(0, 30), replace=False))
        for _ in range(b)
    ]
    rel, counts = _as_padded(relevant_sets)
    out = R.ranking_counts(
        jnp.asarray(topk_idx), jnp.asarray(rel), jnp.asarray(counts)
    )
    want_hr, want_ndcg, want_recall, want_users = np_ranking_metrics(
        topk_idx, relevant_sets
    )
    assert float(out["weight_sum"]) == want_users
    denom = float(out["weight_sum"])
    np.testing.assert_allclose(float(out["hr_sum"]) / denom, want_hr,
                               rtol=1e-6)
    np.testing.assert_allclose(float(out["ndcg_sum"]) / denom, want_ndcg,
                               rtol=1e-6)
    np.testing.assert_allclose(float(out["recall_sum"]) / denom, want_recall,
                               rtol=1e-6)


def test_ranking_counts_pinned_cases():
    # perfect retrieval of a 2-item holdout in the top-2 -> all metrics 1
    out = R.ranking_counts(
        jnp.asarray([[5, 7, 1, 2]], np.int32),
        jnp.asarray([[5, 7]], np.int32),
        jnp.asarray([2], np.int32),
    )
    assert float(out["hr_sum"]) == 1.0
    assert float(out["recall_sum"]) == 1.0
    np.testing.assert_allclose(float(out["ndcg_sum"]), 1.0, rtol=1e-6)
    # single relevant item at the last position of K=4
    out = R.ranking_counts(
        jnp.asarray([[9, 8, 7, 5]], np.int32),
        jnp.asarray([[5]], np.int32),
        jnp.asarray([1], np.int32),
    )
    np.testing.assert_allclose(
        float(out["ndcg_sum"]), (1 / math.log2(5)) / 1.0, rtol=1e-6
    )
    # zero-relevance and zero-weight rows contribute nothing
    out = R.ranking_counts(
        jnp.asarray([[1, 2], [1, 2]], np.int32),
        jnp.asarray([[1, 2], [1, 2]], np.int32),
        jnp.asarray([0, 2], np.int32),
        jnp.asarray([1.0, 0.0], np.float32),
    )
    assert float(out["weight_sum"]) == 0.0
    assert float(out["hr_sum"]) == 0.0


def test_ranking_counts_holdout_larger_than_k():
    # |R_u| > K: IDCG truncates at K, recall divides by |R_u|
    ids = np.asarray([[0, 1, 2]], np.int32)
    rel, counts = _as_padded([[0, 1, 2, 3, 4]])
    out = R.ranking_counts(jnp.asarray(ids), jnp.asarray(rel),
                           jnp.asarray(counts))
    want_hr, want_ndcg, want_recall, _ = np_ranking_metrics(ids, [[0, 1, 2, 3, 4]])
    np.testing.assert_allclose(float(out["ndcg_sum"]), want_ndcg, rtol=1e-6)
    np.testing.assert_allclose(float(out["recall_sum"]), want_recall,
                               rtol=1e-6)
    assert float(out["recall_sum"]) == pytest.approx(3 / 5)


# ---------------------------------------------------------------------------
# relevance building
# ---------------------------------------------------------------------------


def test_relevance_from_dataset_dedup_and_min_rating():
    class DS:
        user = np.asarray([3, 1, 3, 3, 2, 1])
        item = np.asarray([7, 5, 7, 9, 4, 6])
        rating = np.asarray([5.0, 4.0, 5.0, 2.0, 1.0, 5.0])

    users, rel, counts = R.relevance_from_dataset(DS)
    assert users.tolist() == [1, 2, 3]
    assert counts.tolist() == [2, 1, 2]           # (3,7) deduplicated
    assert sorted(rel[2][rel[2] != R.PAD_ITEM].tolist()) == [7, 9]
    users, rel, counts = R.relevance_from_dataset(DS, min_rating=4.0)
    assert users.tolist() == [1, 3]               # user 2 filtered out
    assert counts.tolist() == [2, 1]
    with pytest.raises(ValueError):               # None means no cap, not 0
        R.relevance_from_dataset(DS, max_users=0)


def test_evaluators_accept_precomputed_relevance():
    params, ds = _random_setup(m=20, n=100)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, max_batch=8)
    relevance = R.relevance_from_dataset(ds)
    got = R.evaluate_engine(engine, topk=5, relevance=relevance)
    want = R.evaluate_engine(engine, ds, topk=5)
    assert got == want
    got = R.evaluate_oracle(params, topk=5, relevance=relevance)
    want = R.evaluate_oracle(params, ds, topk=5)
    assert got == want


# ---------------------------------------------------------------------------
# engine parity with the brute-force oracle
# ---------------------------------------------------------------------------


def _random_setup(m=50, n=700, k=16, variant="funk", seed=0):
    params = mf.init_params(
        jax.random.PRNGKey(seed), m, n, k, variant=variant, global_mean=3.0
    )
    ds = synthetic_ratings(num_users=m, num_items=n, num_ratings=1500,
                           seed=seed)
    return params, ds


@pytest.mark.parametrize("variant", ["funk", "bias"])
def test_engine_metrics_match_oracle_exactly_at_threshold_zero(variant):
    params, ds = _random_setup(variant=variant)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, max_batch=32)
    got = R.evaluate_engine(engine, ds, topk=10)
    want = R.evaluate_oracle(params, ds, topk=10)
    assert got == want  # exact equality, not approx: identical indices


def test_engine_metrics_match_oracle_kernel_path_threshold_zero():
    params, ds = _random_setup(n=520)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=True,
                           interpret=True, max_batch=16)
    got = R.evaluate_engine(engine, ds, topk=7, max_users=24)
    want = R.evaluate_oracle(params, ds, topk=7, max_users=24)
    assert got == want


def test_tie_scores_break_to_lower_index_both_paths():
    # factors on a coarse grid: duplicate scores are common, so parity here
    # pins the tie-break (lower item id first) on both sides
    rng = np.random.default_rng(2)
    m, n, k = 20, 150, 8
    p = jnp.asarray(np.round(rng.normal(0, 1, (m, k)) * 2) / 8, jnp.float32)
    q = jnp.asarray(np.round(rng.normal(0, 1, (n, k)) * 2) / 8, jnp.float32)
    params = mf.MFParams(p, q, None, None, None, None)
    ds = synthetic_ratings(num_users=m, num_items=n, num_ratings=400, seed=3)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, max_batch=16)
    got = R.evaluate_engine(engine, ds, topk=10)
    want = R.evaluate_oracle(params, ds, topk=10)
    assert got == want


def test_topk_equals_catalog_size():
    params, ds = _random_setup(m=12, n=40)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, max_batch=8)
    got = R.evaluate_engine(engine, ds, topk=40)   # K == n
    want = R.evaluate_oracle(params, ds, topk=40)
    assert got == want
    # every user's whole holdout is inside the full-catalog ranking
    assert got.hr == 1.0 and got.recall == 1.0


def test_pruned_engine_still_matches_pruned_oracle():
    # same thresholds both sides: the serving layouts introduce no error of
    # their own on top of pruning
    params, ds = _random_setup()
    t = 0.05
    engine = ServingEngine(params, t, t, use_kernel=False, max_batch=32)
    got = R.evaluate_engine(engine, ds, topk=10)
    want = R.evaluate_oracle(params, ds, topk=10, t_p=t, t_q=t)
    assert got == want


def test_dense_own_top_l_relevance_scores_perfect():
    """Relevance = each user's own dense top-L: the dense oracle scores a
    perfect HR and recall, and the engine at threshold 0 scores exactly
    the same — so against this relevance a pruned engine's shortfall is
    the ranking distortion of pruning alone."""
    m, n, k, rel_l = 64, 500, 16, 5
    params = mf.init_params(jax.random.PRNGKey(0), m, n, k,
                            init_method="libmf")
    users = np.arange(m, dtype=np.int32)
    _, top_l = R.dense_topk(params, users, rel_l)
    holdout = types.SimpleNamespace(
        user=np.repeat(users, rel_l),
        item=np.asarray(top_l).reshape(-1),
        rating=np.ones(m * rel_l, np.float32),
    )
    relevance = R.relevance_from_dataset(holdout)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=False, max_batch=32)
    oracle = R.evaluate_oracle(params, topk=10, relevance=relevance)
    assert oracle.hr == oracle.recall == 1.0, oracle
    assert oracle.ndcg == pytest.approx(1.0)
    assert R.evaluate_engine(engine, topk=10, relevance=relevance) == oracle


# ---------------------------------------------------------------------------
# the one-scan epoch variant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t", [0.0, 0.05])
def test_eval_ranking_epoch_scan_matches_oracle(t):
    params, ds = _random_setup()
    batches = R.pack_ranking_batches(ds, 16)
    sums = mf.eval_ranking_epoch_scan(
        params, batches, jnp.float32(t), jnp.float32(t), topk=10
    )
    got = R.report_from_sums(
        {key: float(val) for key, val in sums.items()}, 10
    )
    want = R.evaluate_oracle(params, ds, topk=10, t_p=t, t_q=t)
    assert got.users == want.users
    np.testing.assert_allclose(got.ndcg, want.ndcg, atol=1e-6)
    np.testing.assert_allclose(got.hr, want.hr, atol=1e-6)
    np.testing.assert_allclose(got.recall, want.recall, atol=1e-6)


def test_eval_ranking_epoch_scan_svdpp_history():
    m, n, k = 30, 300, 8
    params = mf.init_params(jax.random.PRNGKey(1), m, n, k, variant="svdpp",
                            global_mean=3.0)
    rng = np.random.default_rng(4)
    hist = rng.integers(0, n, (m, 5)).astype(np.int32)
    ds = synthetic_ratings(num_users=m, num_items=n, num_ratings=500, seed=5)
    batches = R.pack_ranking_batches(ds, 8)
    sums = mf.eval_ranking_epoch_scan(
        params, batches, jnp.float32(0.0), jnp.float32(0.0),
        jnp.asarray(hist), topk=9,
    )
    got = R.report_from_sums(
        {key: float(val) for key, val in sums.items()}, 9
    )
    want = R.evaluate_oracle(params, ds, topk=9, hist=hist)
    np.testing.assert_allclose(got.ndcg, want.ndcg, atol=1e-6)
    assert got.users == want.users


def test_trainer_logs_ranking_metrics():
    ds = synthetic_ratings(num_users=40, num_items=200, num_ratings=1200,
                           seed=0)
    train, test = train_test_split(ds, 0.25, seed=0)
    cfg = TrainConfig(k=8, epochs=2, batch_size=256, pruning_rate=0.3,
                      ranking_topk=10)
    trainer = DPMFTrainer(cfg, train, test)
    history = trainer.run()
    for record in history:
        assert 0.0 <= record.hr <= 1.0
        assert 0.0 <= record.ndcg <= 1.0
        assert 0.0 <= record.recall <= 1.0
    report = trainer.evaluate_ranking()
    assert report.topk == 10
    assert report.ndcg == pytest.approx(history[-1].ndcg)
    # off by default: no ranking fields, no packed batches
    plain = DPMFTrainer(TrainConfig(k=8, epochs=1, batch_size=256), train,
                        test)
    assert plain.evaluate_ranking() is None
    assert math.isnan(plain.run()[-1].ndcg)


# ---------------------------------------------------------------------------
# workload vectors: implicit-trained factors and SASRec session encodings
# ---------------------------------------------------------------------------


def _implicit_trained(seed=0):
    ds = synthetic_ratings(num_users=30, num_items=300, num_ratings=900,
                           seed=seed)
    train, test = train_test_split(ds, 0.25, seed=0)
    cfg = TrainConfig(k=8, epochs=2, batch_size=256, lr=0.02, lam=0.02,
                      pruning_rate=0.3, objective="implicit",
                      implicit_alpha=8.0, implicit_negatives=2, seed=seed)
    trainer = DPMFTrainer(cfg, train, test)
    trainer.run()
    return trainer, test


def test_implicit_trained_engine_matches_oracle_every_path():
    """Factors trained under the WALS objective serve exact top-k parity at
    threshold 0 on the streaming and kernel paths, and at the trained
    thresholds the engine still equals the equally-pruned oracle."""
    trainer, test = _implicit_trained()
    params = trainer.params
    want = R.evaluate_oracle(params, test, topk=10)
    for kw in (dict(use_kernel=False, max_batch=16),
               dict(use_kernel=True, interpret=True, max_batch=16)):
        engine = ServingEngine(params, 0.0, 0.0, **kw)
        assert R.evaluate_engine(engine, test, topk=10) == want, kw
    assert float(trainer.t_p) > 0.0   # calibration really ran
    pruned = ServingEngine(params, trainer.t_p, trainer.t_q,
                           use_kernel=False, max_batch=16)
    got = R.evaluate_engine(pruned, test, topk=10)
    want = R.evaluate_oracle(params, test, topk=10,
                             t_p=trainer.t_p, t_q=trainer.t_q)
    assert got == want


def _session_setup(seed=0, n_items=60, sessions=12):
    from repro.data import clicks
    from repro.models import recsys

    cfg = recsys.SASRecConfig(
        n_items=n_items, embed_dim=16, n_blocks=2, n_heads=2, seq_len=10
    )
    sasrec = recsys.init_sasrec_params(jax.random.PRNGKey(seed), cfg)
    seqs = clicks.sasrec_batch(
        sessions, seq_len=10, n_items=n_items, seed=seed
    )["seq"]
    return cfg, sasrec, jnp.asarray(seqs)


def test_sasrec_session_engine_matches_dense_oracle_every_path():
    from repro.models import recsys
    from repro.workloads import sequential

    cfg, sasrec, seqs = _session_setup()
    view = sequential.session_params(sasrec, seqs, cfg)
    sessions = np.arange(seqs.shape[0], dtype=np.int32)
    want_s, want_i = R.dense_topk(view, sessions, 10, t_p=0.0, t_q=0.0)
    for kw in (dict(use_kernel=False, max_batch=8),
               dict(use_kernel=True, interpret=True, max_batch=8)):
        engine = sequential.session_engine(sasrec, seqs, cfg, **kw)
        scores, ids = sequential.serve_sessions(engine, sessions, topk=10)
        assert np.array_equal(ids, np.asarray(want_i) + 1), kw
        assert np.array_equal(scores, np.asarray(want_s)), kw
    # the dense sasrec_retrieval argsort agrees too (padding row 0 dropped,
    # stable descending order = the same tie contract)
    dense = np.asarray(
        recsys.sasrec_retrieval(sasrec, seqs, cfg, 0.0, use_kernel=False)
    )[:, 1:]
    order = np.argsort(-dense, axis=1, kind="stable")[:, :10].astype(np.int32)
    assert np.array_equal(np.asarray(want_i), order)


def test_sasrec_session_pruned_and_full_catalog():
    """Session serving with a biting item threshold still matches the
    equally-pruned oracle, including topk == n (full catalog ranking)."""
    from repro.workloads import sequential

    cfg, sasrec, seqs = _session_setup(seed=1, n_items=40)
    view = sequential.session_params(sasrec, seqs, cfg)
    n = view.q.shape[0]
    sessions = np.arange(seqs.shape[0], dtype=np.int32)
    t_q = float(np.quantile(np.abs(np.asarray(view.q)), 0.4))
    engine = sequential.session_engine(
        sasrec, seqs, cfg, 0.0, t_q, use_kernel=False, max_batch=8
    )
    scores, ids = sequential.serve_sessions(engine, sessions, topk=n)
    want_s, want_i = R.dense_topk(view, sessions, n, t_p=0.0, t_q=t_q)
    assert np.array_equal(ids, np.asarray(want_i) + 1)
    assert np.array_equal(scores, np.asarray(want_s))
    # full-catalog ranking: every item id exactly once per session
    assert np.array_equal(np.sort(ids, axis=1),
                          np.tile(np.arange(1, n + 1), (len(sessions), 1)))


# ---------------------------------------------------------------------------
# sharded parity (runs meaningfully under the 4-device CI mesh job)
# ---------------------------------------------------------------------------


def test_evaluate_engine_sharded_matches_oracle_4device_mesh():
    """Ranking metrics through ``topk_sharded`` on the forced 4-device CPU
    mesh pin to the dense oracle exactly at t=0, and to the local pruned
    engine at trained thresholds.  Skipped unless the CI serving-mesh job's
    device count is forced."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (run under the 4-device CI mesh job)")
    params, ds = _random_setup(m=33, n=640, k=16)
    for shape, names in [((4,), ("model",)), ((2, 2), ("data", "model"))]:
        mesh = make_mesh(shape, names)
        engine = ServingEngine(params, 0.0, 0.0, use_kernel=False,
                               max_batch=16)
        got = R.evaluate_engine(engine, ds, topk=8, mesh=mesh)
        want = R.evaluate_oracle(params, ds, topk=8)
        assert got == want, (shape, names)
        t = 0.05
        pruned = ServingEngine(params, t, t, use_kernel=False, max_batch=16)
        got = R.evaluate_engine(pruned, ds, topk=8, mesh=mesh)
        want = R.evaluate_engine(pruned, ds, topk=8)
        assert got == want, (shape, names)


def test_workload_vectors_sharded_match_oracle_4device_mesh():
    """The new workload vectors — implicit-trained factors and SASRec
    session encodings — keep exact oracle parity through ``topk_sharded``
    on the forced 4-device CPU mesh (the issue's acceptance bar)."""
    from repro.workloads import sequential

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (run under the 4-device CI mesh job)")
    trainer, test = _implicit_trained(seed=2)
    mesh = make_mesh((4,), ("model",))
    engine = ServingEngine(trainer.params, 0.0, 0.0, use_kernel=False,
                           max_batch=16)
    got = R.evaluate_engine(engine, test, topk=8, mesh=mesh)
    want = R.evaluate_oracle(trainer.params, test, topk=8)
    assert got == want

    cfg, sasrec, seqs = _session_setup(seed=3)
    view = sequential.session_params(sasrec, seqs, cfg)
    sessions = np.arange(seqs.shape[0], dtype=np.int32)
    sengine = sequential.session_engine(
        sasrec, seqs, cfg, use_kernel=False, max_batch=8
    )
    scores, ids = sequential.serve_sessions(
        sengine, sessions, topk=8, mesh=mesh
    )
    want_s, want_i = R.dense_topk(view, sessions, 8, t_p=0.0, t_q=0.0)
    assert np.array_equal(ids, np.asarray(want_i) + 1)
    assert np.array_equal(scores, np.asarray(want_s))
