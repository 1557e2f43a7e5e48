"""Plain float32 reference of SVD++ training with dynamic pruning,
independent of the code under test.

SVD++ (Y. Koren, "Factorization Meets the Neighborhood: a Multifaceted
Collaborative Filtering Model", KDD 2008, Eq. 15 and the SGD updates of its
section 4) predicts

    r_ui = mu + b_u + b_i + q_i . z_u,   z_u = p_u + |N(u)|^-1/2 sum_{j in N(u)} y_j

with N(u) the set of items user u rated.  Like ``bench/reference.py``, whose
rank, threshold, data-order and permutation helpers it uses, it imports
nothing of the program and takes nothing the program made: it derives its
initial tables, its data order and its sets N(u) from the seed and the
training ratings, and follows in straightforward ``jax.numpy``, per
minibatch:

* z_u for each rating; r_u = rank(z_u, t_p) and r_i = rank(q_i, t_q);
* the prediction, whose dot runs over the first min(r_u, r_i) factors;
* the gradients of 0.5 e^2 + 0.5 lam ||.||^2 on those factors only:
  g_p = lam p_u - e q_i, g_q = lam q_i - e z_u, and for each j in N(u)
  g_y_j = lam y_j - e |N(u)|^-1/2 q_i; the biases' g_b = lam b - e, unmasked;
* Adagrad per occurrence, each reading the accumulator as it was before
  the batch, scattered back additively (duplicate rows in a batch add up);
* after the first (dense) epoch, t_p and t_q from P and Q by Eq. 7/8 and
  Algorithm 1's permutation of the latent axis, applied to P, Q and Y and
  to their accumulators.

Departures from the paper, each also the program's:

* the paper defines no pruning for SVD++: pruning on z_u (the user vector
  the prediction uses) is this repository's extension;
* one learning rate and one lam serve every parameter, where Koren tunes
  them per parameter group;
* N(u) holds the user's training items only;
* minibatches with Adagrad, not one rating at a time at a fixed rate.

Initialisation: the seed's key split three ways gives P, Q and Y, each
N(0, scale^2); Y has one more row than the catalog, the padding slot of
the histories, held at zero.  Biases start at zero and mu is the training
ratings' mean.

``dtype`` runs it all in another precision (the lower-precision control);
``fault`` plants a fault ("half_batch": half of each minibatch left out).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (
    _adagrad,
    _leaf_norm,
    _rearrange,
    epoch_order,
    prefix_mask,
    ranks,
    table_threshold,
)

LEAVES = ("p", "q", "y", "b_u", "b_i")
SLOTS = 8   # a history row's width is a multiple of this


def histories(user, item, num_users: int, num_items: int) -> np.ndarray:
    """N(u) for every user: (num_users, H) item ids padded with
    ``num_items``, H the largest set rounded up to a multiple of SLOTS, so
    no set is cut."""
    pairs = np.unique(np.asarray(user, np.int64) * num_items + np.asarray(item, np.int64))
    u, i = pairs // num_items, pairs % num_items
    sizes = np.bincount(u, minlength=num_users)
    width = max(-(-int(sizes.max(initial=1)) // SLOTS) * SLOTS, SLOTS)
    hist = np.full((num_users, width), num_items, np.int32)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    hist[u, np.arange(pairs.size) - starts[u]] = i
    return hist


def init_tables(seed: int, num_users: int, num_items: int, k: int, scale: float):
    """P, Q and Y (with its zero padding row) from PRNGKey(seed)."""
    kp, kq, ky = jax.random.split(jax.random.PRNGKey(seed), 3)
    y = scale * jax.random.normal(ky, (num_items + 1, k), jnp.float32)
    return (
        scale * jax.random.normal(kp, (num_users, k), jnp.float32),
        scale * jax.random.normal(kq, (num_items, k), jnp.float32),
        y.at[num_items].set(0.0),
    )


def user_vectors(p_rows, y, hist_rows):
    """z_u = p_u + |N(u)|^-1/2 sum_{j in N(u)} y_j for the given rows."""
    n = y.shape[0] - 1
    in_set = (hist_rows < n).astype(p_rows.dtype)
    size = jnp.sum(in_set, axis=1, keepdims=True)
    norm = jax.lax.rsqrt(jnp.maximum(size, 1.0))
    return p_rows + norm * jnp.sum(y[hist_rows] * in_set[..., None], axis=1), norm, in_set


def gradients(tables, u, i, r, hist, mu, t_p, t_q, lam):
    """A minibatch's errors, and per leaf its rows and per-occurrence
    gradients, masked to each rating's first min(r_u, r_i) factors."""
    p, q, y, b_u, b_i = tables
    k = p.shape[1]
    h = hist[u]
    pu, qi = p[u], q[i]
    z, norm, in_set = user_vectors(pu, y, h)
    mask = prefix_mask(jnp.minimum(ranks(z, t_p), ranks(qi, t_q)), k, p.dtype)
    err = r - (mu + b_u[u] + b_i[i] + jnp.sum(z * qi * mask, axis=1))
    e = err[:, None]
    g_y = (lam * y[h] - (e * norm * qi)[:, None, :]) * (mask[:, None, :] * in_set[..., None])
    return err, (
        (u, (lam * pu - e * qi) * mask),
        (i, (lam * qi - e * z) * mask),
        (h.reshape(-1), g_y.reshape(-1, k)),
        (u, lam * b_u[u] - err),
        (i, lam * b_i[i] - err),
    )


def step(tables, accs, u, i, r, hist, mu, t_p, t_q, lr, lam):
    """One minibatch: the new tables and accumulators, and the errors."""
    err, grads = gradients(tables, u, i, r, hist, mu, t_p, t_q, lam)
    out = [_adagrad(t, a, idx, g, lr) for t, a, (idx, g) in zip(tables, accs, grads)]
    return tuple(t for t, _ in out), tuple(a for _, a in out), err


@functools.partial(jax.jit, static_argnames=("half_batch",), donate_argnums=(0, 1))
def _epoch(tables, accs, order, user, item, rating, hist, mu, t_p, t_q, lr, lam,
           *, half_batch):
    def body(carry, rows):
        tables, accs, err_sum = carry
        u, i, r = user[rows], item[rows], rating[rows].astype(mu.dtype)
        if half_batch:
            u, i, r = u[: u.shape[0] // 2], i[: i.shape[0] // 2], r[: r.shape[0] // 2]
        tables, accs, err = step(tables, accs, u, i, r, hist, mu, t_p, t_q, lr, lam)
        return (tables, accs, err_sum + jnp.mean(jnp.abs(err)).astype(jnp.float32)), None

    (tables, accs, err_sum), _ = jax.lax.scan(
        body, (tables, accs, jnp.float32(0.0)), order)
    return tables, accs, err_sum / order.shape[0]


def train_readings(cfg: dict, train, seed: int, *, steps: int = 3,
                   dtype=jnp.float32, fault: str = ""):
    """Follow the first ``steps`` epochs of a job from the seed and return
    the readings the comparison uses, per leaf of ``LEAVES``: each epoch's
    mean |err| (``loss``), each leaf's gradient norm after the first epoch
    as Adagrad's accumulator holds it (``grad``), and each leaf's change
    after ``steps`` epochs (``change``), with the thresholds."""
    user, item, rating = train
    m, n, k = cfg["num_users"], cfg["num_items"], cfg["k"]
    num, batch = user.shape[0], cfg["batch_size"]
    hist = jnp.asarray(histories(np.asarray(user), np.asarray(item), m, n))
    p0, q0, y0 = init_tables(seed, m, n, k, cfg["init_scale"])
    tables = tuple(jnp.array(x, dtype, copy=True) for x in (p0, q0, y0)) + (
        jnp.zeros((m,), dtype), jnp.zeros((n,), dtype))
    accs = tuple(jnp.zeros_like(x) for x in tables)
    mu = jnp.asarray(jnp.mean(rating), dtype)
    lr, lam = jnp.asarray(cfg["lr"], dtype), jnp.asarray(cfg["lam"], dtype)
    t_p = t_q = 0.0
    perm = jnp.arange(k)
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for epoch in range(steps):
            order = epoch_order(seed, epoch, num, num // batch, batch)
            tables, accs, loss = _epoch(
                tables, accs, order, user, item, rating, hist, mu,
                jnp.asarray(t_p, dtype), jnp.asarray(t_q, dtype), lr, lam,
                half_batch=fault == "half_batch",
            )
            out["loss"].append(float(loss))
            if epoch == 0:
                out["grad"] = {leaf: math.sqrt(float(jnp.sum(a.astype(jnp.float32))))
                               for leaf, a in zip(LEAVES, accs)}
                p, q = (x.astype(jnp.float32) for x in tables[:2])
                t_p = table_threshold(p, cfg["pruning_rate"])
                t_q = table_threshold(q, cfg["pruning_rate"])
                perm = _rearrange(p, q, t_p, t_q)
                tables = tuple(x[:, perm] for x in tables[:3]) + tables[3:]
                accs = tuple(x[:, perm] for x in accs[:3]) + accs[3:]
    start = (p0[:, perm], q0[:, perm], y0[:, perm], 0.0, 0.0)
    out["change"] = {leaf: _leaf_norm(x.astype(jnp.float32) - x0)
                     for leaf, x, x0 in zip(LEAVES, tables, start)}
    out["t_p"], out["t_q"], out["perm"] = t_p, t_q, np.asarray(perm)
    return out
