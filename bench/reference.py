"""Plain float32 reference of DP-MF, independent of the code under test.

It imports nothing of the program and takes nothing that the program made:
it derives its own initial factors and data order from the seed, as the
configuration states them, and follows the paper's procedure step by step
in straightforward ``jax.numpy``:

* effective rank of a row: the index of its first entry with |v| < T
  (k when there is none) -- the early stop of Algorithms 2 and 3;
* a training step: for each rating in a minibatch, the dot over the first
  min(r_u, r_i) factors, the squared-error gradient with L2 penalty lam on
  those factors only, and Adagrad on the gathered rows, scattered back
  additively (duplicate rows in a batch add up);
* after the first (dense) epoch, thresholds from Eq. 7/8 on the fitted
  normal of each table, solved in float64 on the host, and Algorithm 1's
  permutation of the latent axis by ascending joint sparsity;
* scoring: masked scores of every item for a block of users, float32-exact
  ("highest") or at the three-pass bfloat16 arithmetic of Precision.HIGH
  ("bf16_3x", the lower-precision control).

``dtype`` runs the whole training reference in another precision (the
lower-precision control); ``fault`` plants a fault in it ("half_batch":
half of each minibatch left out, the mean taken over the rest).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAGRAD_EPS = 1e-8


def ranks(rows, t):
    """First index with |v| < t per row (k if none); t == 0 gives k."""
    insig = jnp.abs(rows) < t
    k = rows.shape[-1]
    first = jnp.argmax(insig, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.any(insig, axis=-1), first, jnp.int32(k))


def prefix_mask(r, k, dtype=jnp.float32):
    return (jnp.arange(k, dtype=jnp.int32)[None, :] < r[:, None]).astype(dtype)


def _normal_cdf(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def threshold(mu: float, sigma: float, rate: float) -> float:
    """Eq. 7/8: T = sigma * x + mu with Phi(x) - Phi(-x - 2 mu / sigma) = rate,
    by bisection in float64."""
    if rate <= 0.0:
        return 0.0
    lo, hi = -mu / sigma, max(-mu / sigma, 0.0) + 16.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        frac = _normal_cdf(mid) - _normal_cdf(-mid - 2.0 * mu / sigma)
        lo, hi = (mid, hi) if frac < rate else (lo, mid)
    return max(sigma * 0.5 * (lo + hi) + mu, 0.0)


def table_threshold(table, rate: float) -> float:
    m = table.astype(jnp.float32)
    return threshold(float(jnp.mean(m)), float(jnp.std(m)), rate)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def init_tables(seed: int, num_users: int, num_items: int, k: int, scale: float):
    """The configuration's initial factors: N(0, scale^2) from PRNGKey(seed),
    split three ways (user, item, and an unused implicit table)."""
    kp, kq, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        scale * jax.random.normal(kp, (num_users, k), jnp.float32),
        scale * jax.random.normal(kq, (num_items, k), jnp.float32),
    )


def epoch_order(seed: int, epoch: int, n: int, steps: int, batch: int):
    """The configuration's data order: a permutation keyed on
    fold_in(PRNGKey(seed), epoch), remainder dropped."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
    return jax.random.permutation(key, n)[: steps * batch].reshape(steps, batch)


def _adagrad(table, acc, idx, g, lr):
    acc_rows = acc[idx] + g * g
    delta = -lr * g / jnp.sqrt(acc_rows + ADAGRAD_EPS)
    return table.at[idx].add(delta), acc.at[idx].add(g * g)


@functools.partial(jax.jit, static_argnames=("half_batch",), donate_argnums=(0,))
def _epoch(state, order, user, item, rating, t_p, t_q, lr, lam, *, half_batch):
    def step(carry, rows):
        p, q, ap, aq, err_sum = carry
        u, i, r = user[rows], item[rows], rating[rows].astype(p.dtype)
        if half_batch:
            u, i, r = u[: u.shape[0] // 2], i[: i.shape[0] // 2], r[: r.shape[0] // 2]
        pu, qi = p[u], q[i]
        k = p.shape[1]
        mask = prefix_mask(jnp.minimum(ranks(pu, t_p), ranks(qi, t_q)), k, p.dtype)
        err = r - jnp.sum(pu * qi * mask, axis=1)
        g_p = (lam * pu - err[:, None] * qi) * mask
        g_q = (lam * qi - err[:, None] * pu) * mask
        p, ap = _adagrad(p, ap, u, g_p, lr)
        q, aq = _adagrad(q, aq, i, g_q, lr)
        return (p, q, ap, aq, err_sum + jnp.mean(jnp.abs(err)).astype(jnp.float32)), None

    p, q, ap, aq = state
    (p, q, ap, aq, err_sum), _ = jax.lax.scan(
        step, (p, q, ap, aq, jnp.float32(0.0)), order
    )
    return (p, q, ap, aq), err_sum / order.shape[0]


def _rearrange(p, q, t_p, t_q):
    """Algorithm 1: the latent permutation by ascending joint sparsity."""
    sp_p = jnp.mean((jnp.abs(p) < t_p).astype(jnp.float32), axis=0)
    sp_q = jnp.mean((jnp.abs(q) < t_q).astype(jnp.float32), axis=0)
    return jnp.argsort(sp_p * sp_q, stable=True)


def _leaf_norm(x):
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def train_readings(cfg: dict, train, seed: int, *, steps: int = 3,
                   dtype=jnp.float32, fault: str = ""):
    """Follow the first ``steps`` epochs of a job from the seed and return the
    readings the comparison uses: each epoch's mean |err| (``loss``), each
    table's gradient norm after the first epoch as Adagrad's accumulator
    holds it (``grad``), and each table's change after ``steps`` epochs
    (``change``), with the thresholds."""
    user, item, rating = train
    n, batch, k = user.shape[0], cfg["batch_size"], cfg["k"]
    n_steps = n // batch
    p0, q0 = init_tables(seed, cfg["num_users"], cfg["num_items"], k, cfg["init_scale"])
    state = (jnp.array(p0, dtype, copy=True), jnp.array(q0, dtype, copy=True),
             jnp.zeros_like(p0, dtype), jnp.zeros_like(q0, dtype))
    lr, lam = jnp.asarray(cfg["lr"], dtype), jnp.asarray(cfg["lam"], dtype)
    t_p = t_q = 0.0
    perm = jnp.arange(k)
    out = {"loss": []}
    for epoch in range(steps):
        order = epoch_order(seed, epoch, n, n_steps, batch)
        state, loss = _epoch(
            state, order, user, item, rating,
            jnp.asarray(t_p, dtype), jnp.asarray(t_q, dtype), lr, lam,
            half_batch=fault == "half_batch",
        )
        out["loss"].append(float(loss))
        if epoch == 0:
            p, q, ap, aq = state
            out["grad"] = {"p": math.sqrt(float(jnp.sum(ap.astype(jnp.float32)))),
                           "q": math.sqrt(float(jnp.sum(aq.astype(jnp.float32))))}
            t_p = table_threshold(p, cfg["pruning_rate"])
            t_q = table_threshold(q, cfg["pruning_rate"])
            perm = _rearrange(p.astype(jnp.float32), q.astype(jnp.float32), t_p, t_q)
            state = tuple(x[:, perm] for x in state)
    p, q = state[0], state[1]
    out["change"] = {"p": _leaf_norm(p.astype(jnp.float32) - p0[:, perm]),
                     "q": _leaf_norm(q.astype(jnp.float32) - q0[:, perm])}
    out["t_p"], out["t_q"], out["perm"] = t_p, t_q, np.asarray(perm)
    return out


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------


def _split_bf16(x):
    # reduce_precision, not a round trip through bfloat16: XLA may drop a
    # convert pair under its excess-precision rule, in one use and not in
    # another, and the parts would then not add up to x
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _dot(a, b, precision: str):
    """``a @ b.T`` in float32: "highest" is float32-accurate; "bf16_3x" is
    the arithmetic of Precision.HIGH -- each operand split into two bfloat16
    parts and three of the four part products summed -- written out, so that
    it reads the same on every backend."""
    def mm(x, y):
        return jnp.dot(x, y.T, precision="highest", preferred_element_type=jnp.float32)

    if precision == "highest":
        return mm(a, b)
    if precision == "bf16_3x":
        a_hi, a_lo = _split_bf16(a)
        b_hi, b_lo = _split_bf16(b)
        return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("topk", "precision"))
def score_block(p_rows, q, t_p, t_q, items, *, topk: int, precision: str):
    """For a block of users: the reference scores of ``items`` (B, K), the
    k-th best reference score, the scale sum_t |p_t q_t| over the same
    prefix for each of ``items``, and the reference's own top-k
    ``(scores, indices)``."""
    k = p_rows.shape[1]
    r_u, r_i = ranks(p_rows, t_p), ranks(q, t_q)
    pm = p_rows * prefix_mask(r_u, k)
    qm = q * prefix_mask(r_i, k)
    s = _dot(pm, qm, precision)
    top_s, top_i = jax.lax.top_k(s, topk)
    picked = jnp.take_along_axis(s, items, axis=1)
    scale = jnp.einsum("bk,bjk->bj", jnp.abs(pm), jnp.abs(qm[items]),
                       precision="highest")
    return picked, top_s[:, -1], scale, top_s, top_i
