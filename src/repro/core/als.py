"""Implicit ALS (Hu, Koren & Volinsky, ICDM 2008) with dynamic pruning.

The loss (Hu et al., Eq. 3) runs over every user x item pair:

    sum_ui c_ui (p_ui - x~_u . y~_i)^2
        + lam (sum_u n_u ||x_u||^2 + sum_i n_i ||y_i||^2)

with p_ui = 1 for an observed pair and 0 otherwise, c_ui = 1 + alpha r_ui
(1 where unobserved), and lam scaled by each row's count of observed pairs
n_u, as Spark MLlib's ``ALS(implicitPrefs=True)`` scales ``regParam``
(Zhou et al. 2008, ALS-WR).  x~ and y~ are rows masked by their own
effective ranks (``core/ranks.mask_rows``), the paper's early stop.

An epoch is two half-steps, user rows and then item rows.  With the fixed
side Y held, each row with pairs solves (Hu et al., section 4)

    (Y~'Y~ + Y~'(C_u - I)Y~ + lam n_u I) x_u = Y~'C_u p(u)

Pruning: with r_u the rank of the row's current x_u, only x_u[:r_u] enters
the loss, and the coordinates from r_u on are held (Algorithm 3).  The
system's rows and columns from r_u on are replaced by the identity's and
the right-hand side's entries by the old x_u[r_u:], so the one batched
solve returns the leading block's minimiser and the held tail.  At rate 0
every rank is k and this is plain iALS.  A row without pairs keeps its
factors.

The half-step is four programs, named so that a device trace tells them
apart: ``als_gram_fixed`` (the fixed side's masked Gramian Y~'Y~, once),
``als_gram`` (a row block's sums of (c - 1) y~ y~' and c y~, chunk by chunk
of ``data/pairs.py``'s layout), ``als_solve`` (the systems, their Cholesky
factors, the substitutions and the row write) and ``als_objective`` (the
loss).  Products and accumulations run in float32 at ``HIGHEST``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.ranks import effective_ranks, mask_rows, rank_mask
from repro.data.pairs import PairLayouts, SideLayout

HIGHEST = jax.lax.Precision.HIGHEST


@jax.jit
def als_gram_fixed(fixed: jax.Array, t_fixed: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The fixed side's masked rows and their Gramian."""
    masked = mask_rows(fixed, t_fixed)
    return masked, jnp.dot(masked.T, masked, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("rows", "launch"))
def als_gram(masked, row, col, conf, start, launches, lo, *, rows: int, launch: int):
    """Sums of (c - 1) y~ y~' and c y~ over the pairs of rows
    ``[lo, lo + rows)``, read ``launch`` chunks at a time from chunk
    ``start``: each chunk's partial is added to its row's sum."""
    k = masked.shape[1]

    def body(j, acc):
        a, b = acc
        c0 = start + j * launch
        seg = jax.lax.dynamic_slice(row, (c0,), (launch,)) - lo
        ids = jax.lax.dynamic_slice(col, (c0, 0), (launch, col.shape[1]))
        c = jax.lax.dynamic_slice(conf, (c0, 0), (launch, conf.shape[1]))
        f = masked[ids]                                   # (launch, chunk, k)
        weighted = f * jnp.where(c > 0, c - 1.0, 0.0)[..., None]
        part_a = jax.lax.dot_general(weighted, f, (((1,), (1,)), ((0,), (0,))),
                                     precision=HIGHEST)
        part_b = jnp.einsum("nc,nck->nk", c, f, precision=HIGHEST)
        # chunks past the block's rows (the next block's, or padding) drop
        seg = jnp.where((seg >= 0) & (seg < rows), seg, rows)
        return (a.at[seg].add(part_a, mode="drop", indices_are_sorted=True),
                b.at[seg].add(part_b, mode="drop", indices_are_sorted=True))

    zeros = (jnp.zeros((rows, k, k), jnp.float32), jnp.zeros((rows, k), jnp.float32))
    return jax.lax.fori_loop(0, launches, body, zeros)


@functools.partial(jax.jit, donate_argnums=(0,))
def als_solve(x, a, b, gram, count, lam, rank_sum, lo, first, t_x):
    """Solve rows ``[lo + first, lo + rows)`` of ``x`` from their sums
    ``a``, ``b`` and the fixed side's Gramian; write them back.  Returns
    the table and ``rank_sum`` plus the solved rows' ranks."""
    rows, k = b.shape
    old = jax.lax.dynamic_slice(x, (lo, 0), (rows, k))
    n = jax.lax.dynamic_slice(count, (lo,), (rows,))
    r = effective_ranks(old, t_x)
    keep = rank_mask(r, k)                                # (rows, k)
    inside = keep[:, :, None] * keep[:, None, :]
    eye = jnp.eye(k, dtype=jnp.float32)
    system = (gram + a + lam * n[:, None, None] * eye) * inside + eye * (1.0 - inside)
    rhs = (b * keep + old * (1.0 - keep))[..., None]
    chol = jnp.linalg.cholesky(system)
    y = jax.lax.linalg.triangular_solve(chol, rhs, left_side=True, lower=True)
    sol = jax.lax.linalg.triangular_solve(chol, y, left_side=True, lower=True,
                                          transpose_a=True)[..., 0]
    solved = (jnp.arange(rows) >= first) & (n > 0)
    new = jnp.where(solved[:, None] & (keep > 0), sol, old)
    x = jax.lax.dynamic_update_slice(x, new, (lo, 0))
    return x, rank_sum + jnp.sum(jnp.where(solved, r, 0))


def half_step(x, fixed, t_x, t_fixed, side: SideLayout, *, lam: float):
    """Solve every row of ``x`` that has pairs against the fixed table,
    block by block.  Returns the new table and the sum of the solved
    rows' ranks (a device scalar)."""
    masked, gram = als_gram_fixed(fixed, t_fixed)
    rank_sum = jnp.zeros((), jnp.int32)
    for blk in side.blocks:
        a, b = als_gram(masked, side.row, side.col, side.conf, blk.start,
                        blk.launches, blk.lo, rows=side.rows_per_block,
                        launch=side.chunks_per_launch)
        x, rank_sum = als_solve(x, a, b, gram, side.count, lam, rank_sum,
                                blk.lo, blk.first, t_x)
    return x, rank_sum


@functools.partial(jax.jit, static_argnames=("launch",))
def als_objective(p, q, t_p, t_q, row, col, conf, count_u, count_i, lam, *, launch: int):
    """The loss above, the user side's chunks giving the observed pairs:
    sum over them of c (1 - s)^2 - s^2, s = x~ . y~, plus the sum of s^2
    over all pairs by the Gramian trick, <X~'X~, Y~'Y~>, plus the penalty."""
    pt, qt = mask_rows(p, t_p), mask_rows(q, t_q)
    m = p.shape[0]

    def body(j, total):
        c0 = j * launch
        r = jax.lax.dynamic_slice(row, (c0,), (launch,))
        ids = jax.lax.dynamic_slice(col, (c0, 0), (launch, col.shape[1]))
        c = jax.lax.dynamic_slice(conf, (c0, 0), (launch, conf.shape[1]))
        s = jnp.einsum("nk,nck->nc", pt[jnp.minimum(r, m - 1)], qt[ids], precision=HIGHEST)
        return total + jnp.sum(jnp.where(c > 0, c * (1.0 - s) ** 2 - s * s, 0.0))

    launches = -(-(row.shape[0] - launch) // launch)
    observed = jax.lax.fori_loop(0, launches, body, jnp.zeros((), jnp.float32))
    every = jnp.sum(jnp.dot(pt.T, pt, precision=HIGHEST) * jnp.dot(qt.T, qt, precision=HIGHEST))
    penalty = lam * (jnp.sum(count_u * jnp.sum(p * p, axis=1))
                     + jnp.sum(count_i * jnp.sum(q * q, axis=1)))
    return observed + every + penalty


def objective(p, q, t_p, t_q, layouts: PairLayouts, *, lam: float):
    """The loss of tables ``p`` and ``q`` on ``layouts``' pairs."""
    user = layouts.user
    return als_objective(p, q, t_p, t_q, user.row, user.col, user.conf,
                         user.count, layouts.item.count, lam,
                         launch=user.chunks_per_launch)

