"""The seeded generator on the CPU at a small size."""
import jax
import numpy as np

from bench import data

CFG = {
    "num_users": 400, "num_items": 300, "num_ratings": 60000,
    "rating_min": 0.5, "rating_max": 5.0, "rating_step": 0.5,
    "test_fraction": 0.2, "k": 16, "init_scale": 0.1,
    "law": {"k_true": 8, "spectrum_decay": 0.7, "noise": 0.35,
            "bias_std": 0.25, "item_zipf": 1.3},
}


def test_counts_range_and_step():
    (u, i, r), (tu, ti, tr) = data.ratings(CFG, 7)
    assert u.shape == i.shape == r.shape == (48000,)
    assert tu.shape == (12000,)
    for users, items, rating in ((u, i, r), (tu, ti, tr)):
        users, items, rating = map(np.asarray, (users, items, rating))
        assert users.min() >= 0 and users.max() < CFG["num_users"]
        assert items.min() >= 0 and items.max() < CFG["num_items"]
        assert rating.min() >= 0.5 and rating.max() <= 5.0
        np.testing.assert_array_equal(rating * 2, np.round(rating * 2))


def test_same_seed_same_inputs_and_other_seed_other_inputs():
    a = data.ratings(CFG, 2**33 + 5)
    b = data.ratings(CFG, 2**33 + 5)
    c = data.ratings(CFG, 2**33 + 6)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0][1]), np.asarray(c[0][1]))


def test_item_popularity_follows_truncated_zipf():
    (_, i, _), (_, ti, _) = data.ratings(CFG, 3)
    items = np.concatenate([np.asarray(i), np.asarray(ti)])
    share = np.bincount(items, minlength=CFG["num_items"]) / len(items)
    pmf = np.arange(1, CFG["num_items"] + 1) ** -1.3
    pmf /= pmf.sum()
    # the head of the law, item by item, within 4 standard errors
    se = np.sqrt(pmf[:10] * (1 - pmf[:10]) / len(items))
    assert np.all(np.abs(share[:10] - pmf[:10]) < 4 * se)
    # and the tail's mass as a whole
    assert abs(share[100:].sum() - pmf[100:].sum()) < 0.01


def test_users_are_uniform():
    (u, _, _), _ = data.ratings(CFG, 4)
    counts = np.bincount(np.asarray(u), minlength=CFG["num_users"])
    assert abs(counts.mean() - 120) < 1e-9 and counts.std() < 4 * np.sqrt(120)


def test_sub_seeds_take_large_seeds():
    a = data.sub_seeds(2**40 + 3, 3)
    assert len(set(a)) == 3 and all(0 <= s < 2**31 for s in a)
    assert a == data.sub_seeds(2**40 + 3, 3)


def test_factor_tables_shape_and_scale():
    p, q = data.factor_tables(CFG, 1)
    assert p.shape == (400, 16) and q.shape == (300, 16)
    assert abs(float(np.asarray(p).std()) - 0.1) < 0.01
