"""Data pipeline determinism + embedding-bag/optimizer correctness."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis_compat import given, settings, st

from repro.data import loader
from repro.data.graphs import synthetic_graph, to_csr, neighbor_sample
from repro.data.ratings import synthetic_ratings, build_user_history
from repro.models.recsys import embedding_bag
from repro.optim.optimizers import RowOptimizer


def test_loader_deterministic_and_resumable():
    ds = synthetic_ratings(50, 60, 1000, seed=0)
    a = list(loader.iterate_batches(ds, 128, seed=3, epoch=2))
    b = list(loader.iterate_batches(ds, 128, seed=3, epoch=2))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["user"], y["user"])
    # resume mid-epoch
    c = list(loader.iterate_batches(ds, 128, seed=3, epoch=2, start_step=3))
    np.testing.assert_array_equal(a[3]["user"], c[0]["user"])
    # different epoch -> different order
    d = next(iter(loader.iterate_batches(ds, 128, seed=3, epoch=4)))
    assert not np.array_equal(a[0]["user"], d["user"])


def test_loader_eval_padding_weights():
    ds = synthetic_ratings(50, 60, 1000, seed=0)
    batches = list(loader.iterate_batches(ds, 300, shuffle=False,
                                          drop_remainder=False))
    assert len(batches) == 4
    assert batches[-1]["weight"].sum() == 1000 - 3 * 300
    assert all(b["user"].shape == (300,) for b in batches)


def test_user_history_padding():
    ds = synthetic_ratings(20, 30, 500, seed=0)
    hist, _ = build_user_history(ds, max_hist=8)
    assert hist.shape == (20, 8)
    assert hist.max() <= 30  # padding value == num_items


def test_user_history_is_the_set_of_rated_items():
    """Koren's N(u): each user's distinct items, in ascending id order,
    cut at max_hist; against a plain loop, on data with repeated pairs."""
    rng = np.random.default_rng(3)
    ds = synthetic_ratings(40, 30, 900, seed=1)
    again = rng.choice(len(ds), 300)   # repeat 300 (user, item) pairs
    ds.user = np.concatenate([ds.user, ds.user[again]])
    ds.item = np.concatenate([ds.item, ds.item[again]])
    ds.rating = np.concatenate([ds.rating, ds.rating[again]])
    hist, truncated = build_user_history(ds, max_hist=12)
    want = np.full((40, 12), 30, np.int32)
    cut = 0
    for u in range(40):
        items = sorted({int(i) for uu, i in zip(ds.user, ds.item) if uu == u})
        cut += len(items) > 12
        want[u, : min(len(items), 12)] = items[:12]
    np.testing.assert_array_equal(hist, want)
    assert truncated == cut > 0


def test_neighbor_sampler_is_valid_subgraph():
    g = synthetic_graph(300, 2000, 8, seed=0)
    indptr, indices = to_csr(g.edges, g.num_nodes)
    seeds = np.arange(10)
    nodes, edges_local, n_seeds = neighbor_sample(indptr, indices, seeds, [4, 3], seed=1)
    assert n_seeds == 10
    assert (nodes[:10] == seeds).all()
    real = edges_local[edges_local[:, 0] >= 0]
    # every local edge maps to a real global edge
    edge_set = {(int(s), int(d)) for s, d in g.edges}
    for src_l, dst_l in real[:200]:
        assert (int(nodes[src_l]), int(nodes[dst_l])) in edge_set
    # fanout respected: each dst draws at most fanout distinct srcs per layer
    assert len(real) <= 10 * 4 + (len(nodes) - 10) * 3


@given(
    st.integers(2, 40),   # vocab
    st.integers(1, 6),    # dim
    st.integers(1, 30),   # nnz
    st.integers(1, 8),    # bags
    st.sampled_from(["sum", "mean"]),
)
@settings(max_examples=30, deadline=None)
def test_embedding_bag_equals_onehot_matmul(vocab, dim, nnz, bags, combiner):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(0, 1, (vocab, dim)).astype(np.float32))
    values = jnp.asarray(rng.integers(0, vocab, nnz), jnp.int32)
    segments = jnp.asarray(np.sort(rng.integers(0, bags, nnz)), jnp.int32)
    out = embedding_bag(table, values, segments, bags, combiner=combiner)

    onehot = jax.nn.one_hot(values, vocab)  # (nnz, V)
    seg_onehot = jax.nn.one_hot(segments, bags).T  # (bags, nnz)
    expected = seg_onehot @ (onehot @ table)
    if combiner == "mean":
        counts = np.maximum(np.bincount(np.asarray(segments), minlength=bags), 1)
        expected = expected / counts[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name", ["sgd", "adagrad", "adadelta", "adam"])
def test_row_optimizer_masked_update(name):
    """Masked coordinates never move; unmasked ones follow the update rule."""
    opt = RowOptimizer(name=name)
    param = jnp.ones((10, 4))
    state = opt.init(param)
    idx = jnp.asarray([2, 5])
    grad = jnp.ones((2, 4))
    mask = jnp.asarray([[1.0, 1, 0, 0], [1, 1, 1, 1]])
    new_param, _ = opt.apply_rows(param, state, idx, grad, mask, 0.1)
    np.testing.assert_array_equal(np.asarray(new_param[2, 2:]), [1.0, 1.0])
    assert float(new_param[2, 0]) < 1.0
    assert float(new_param[5, 3]) < 1.0
    untouched = np.delete(np.arange(10), [2, 5])
    np.testing.assert_array_equal(np.asarray(new_param[untouched]), 1.0)


def test_row_sgd_matches_closed_form():
    opt = RowOptimizer(name="sgd")
    param = jnp.zeros((4, 3))
    idx = jnp.asarray([1, 1])  # duplicate rows accumulate
    grad = jnp.ones((2, 3))
    mask = jnp.ones((2, 3))
    new_param, _ = opt.apply_rows(param, {}, idx, grad, mask, 0.5)
    np.testing.assert_allclose(np.asarray(new_param[1]), -1.0)  # 2 * -0.5


def test_row_adagrad_matches_closed_form():
    opt = RowOptimizer(name="adagrad", eps=0.0)
    param = jnp.zeros((2, 2))
    state = opt.init(param)
    idx = jnp.asarray([0])
    grad = 2.0 * jnp.ones((1, 2))
    mask = jnp.ones((1, 2))
    p1, s1 = opt.apply_rows(param, state, idx, grad, mask, 0.1)
    # delta = -lr * g / sqrt(g^2) = -lr
    np.testing.assert_allclose(np.asarray(p1[0]), -0.1, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s1["acc"][0]), 4.0)
