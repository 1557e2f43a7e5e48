"""Seeded inputs of a benchmark run, made on the device from ``--seed``.

``ratings`` follows the law of the repository's synthetic rating generator
(``data/ratings.synthetic_ratings``): a planted low-rank signal with a
decaying factor spectrum, Gaussian user and item biases, uniform users,
items drawn from a Zipf law truncated to the catalog (item 0 the most
popular), Gaussian noise, clipped to the configuration's rating scale and
rounded to its step.  The law is copied here, not imported, and drawn with
``jax.random`` in one jitted call, so the full MovieLens-25M shape takes
seconds instead of half a minute of host work.

``factor_tables`` makes the factor tables that the serving cells score:
entries i.i.d. N(0, scale^2), the trainer's own initialisation law.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_CHUNK = 1 << 20   # ratings per chunk of the planted dot product


def sub_seeds(seed: int, n: int) -> list:
    """``n`` independent 31-bit seeds derived from any non-negative integer
    (a run's seed may not fit in 32 signed bits)."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(s) >> 1 for s in state]


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_users", "num_items", "num_ratings", "k_true", "spectrum_decay",
        "noise", "bias_std", "item_zipf", "rating_min", "rating_max",
        "rating_step",
    ),
)
def _ratings(
    key, *, num_users, num_items, num_ratings, k_true, spectrum_decay, noise,
    bias_std, item_zipf, rating_min, rating_max, rating_step,
):
    kp, kq, kbu, kbi, ku, ki, kn = jax.random.split(key, 7)
    spectrum = jnp.arange(1, k_true + 1, dtype=jnp.float32) ** -spectrum_decay
    spectrum = spectrum * jnp.sqrt(k_true / jnp.sum(spectrum ** 2))
    scale = spectrum / np.sqrt(k_true)
    p_true = jax.random.normal(kp, (num_users, k_true)) * scale
    q_true = jax.random.normal(kq, (num_items, k_true)) * scale
    u_bias = jax.random.normal(kbu, (num_users,)) * bias_std
    i_bias = jax.random.normal(kbi, (num_items,)) * bias_std

    users = jax.random.randint(ku, (num_ratings,), 0, num_users, jnp.int32)
    # Zipf(item_zipf) truncated to [1, num_items] by inverse CDF: the law of
    # rejecting numpy's unbounded Zipf draws above the catalog size
    pmf = jnp.arange(1, num_items + 1, dtype=jnp.float32) ** -item_zipf
    cdf = jnp.cumsum(pmf) / jnp.sum(pmf)
    u01 = jax.random.uniform(ki, (num_ratings,))
    items = jnp.minimum(
        jnp.searchsorted(cdf, u01, side="right"), num_items - 1
    ).astype(jnp.int32)

    pad = (-num_ratings) % _CHUNK
    uu = jnp.pad(users, (0, pad)).reshape(-1, _CHUNK)
    ii = jnp.pad(items, (0, pad)).reshape(-1, _CHUNK)
    dots = jax.lax.map(
        lambda ui: jnp.sum(p_true[ui[0]] * q_true[ui[1]], axis=1), (uu, ii)
    ).reshape(-1)[:num_ratings]

    mid = 0.5 * (rating_min + rating_max)
    spread = 0.5 * (rating_max - rating_min)
    raw = (
        mid + spread * dots + 0.5 * (u_bias[users] + i_bias[items])
        + noise * jax.random.normal(kn, (num_ratings,))
    )
    r = jnp.clip(raw, rating_min, rating_max)
    r = rating_min + jnp.round((r - rating_min) / rating_step) * rating_step
    return users, items, r.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_train",))
def _split(key, users, items, rating, *, num_train):
    perm = jax.random.permutation(key, users.shape[0])
    tr, te = perm[:num_train], perm[num_train:]
    return (users[tr], items[tr], rating[tr]), (users[te], items[te], rating[te])


def ratings(cfg: dict, seed: int):
    """``(train, test)``, each ``(user, item, rating)`` device arrays, drawn
    from ``seed`` by the configuration's law and split by its test share."""
    law = cfg["law"]
    k_data, k_split = jax.random.split(jax.random.PRNGKey(seed))
    users, items, rating = _ratings(
        k_data,
        num_users=cfg["num_users"], num_items=cfg["num_items"],
        num_ratings=cfg["num_ratings"], k_true=law["k_true"],
        spectrum_decay=law["spectrum_decay"], noise=law["noise"],
        bias_std=law["bias_std"], item_zipf=law["item_zipf"],
        rating_min=cfg["rating_min"], rating_max=cfg["rating_max"],
        rating_step=cfg["rating_step"],
    )
    num_train = int(cfg["num_ratings"] * (1.0 - cfg["test_fraction"]))
    return _split(k_split, users, items, rating, num_train=num_train)


@functools.partial(jax.jit, static_argnames=("num_users", "num_items", "k"))
def _tables(key, scale, *, num_users, num_items, k):
    kp, kq = jax.random.split(key)
    return (
        scale * jax.random.normal(kp, (num_users, k), jnp.float32),
        scale * jax.random.normal(kq, (num_items, k), jnp.float32),
    )


def factor_tables(cfg: dict, seed: int):
    """(P, Q) float32 factor tables on the device, from ``seed``."""
    return _tables(
        jax.random.PRNGKey(seed), jnp.float32(cfg["init_scale"]),
        num_users=cfg["num_users"], num_items=cfg["num_items"], k=cfg["k"],
    )
