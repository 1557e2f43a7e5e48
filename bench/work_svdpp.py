"""Work an SVD++ training step requires, counted at pair ranks from the
benchmark's own batches and sets N(u) (``bench/reference_svdpp.py``),
never from what the program executes.

A rating's pair rank is min(r_u, r_i), r_u the effective rank of z_u and
r_i that of q_i.  A history occurrence (j in N(u) for a rating of u) is
live when the rating's pair rank is above 0.

* FLOPs: funk's terms per rating (``bench/work.py``), and per live
  history occurrence and factor of its pair rank: adding y_j into z_u (1),
  its gradient lam y_j - c q_i with c = e |N(u)|^-1/2 per rating (3), and
  Adagrad on its row (7).
* Bytes: each distinct row of P, Q and Y that a step touches, over the
  largest pair rank among its occurrences, with its Adagrad row, each read
  and written once; the ratings' ids and values; and the ids of N(u) once
  for each distinct user of the step.

An implementation that merges duplicate rows moves no fewer bytes than
these, so a share of the roofline computed from them cannot pass 100%
unless the time leaves out part of the work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.work import F32, INDEX_BYTES, TRAIN_BYTES_PER_RATING, TRAIN_FLOPS_PER_FACTOR

IMPLICIT_FLOPS_PER_FACTOR = 1 + 3 + 7
# a row and its Adagrad row, each read and written once
ROW_BYTES_PER_FACTOR = 4 * F32


def _row_max_sum(ids, rank, k: int):
    """Sum over the distinct ``ids`` of the largest ``rank`` among each
    id's occurrences (ranks in [0, k])."""
    key = jnp.sort(ids * (k + 1) + rank)
    row = key // (k + 1)
    last = jnp.concatenate([row[1:] != row[:-1], jnp.ones((1,), bool)])
    return jnp.sum(jnp.where(last, key % (k + 1), 0))


@functools.partial(jax.jit, static_argnames=("k",))
def step_counts(users, items, hist, r_user, r_item, *, k: int):
    """(FLOPs, bytes) of each step of ``(steps, B)`` batches, as int32
    arrays of length steps."""
    n = r_item.shape[0]
    sizes = jnp.sum(hist < n, axis=1).astype(jnp.int32)

    def one(batch):
        u, i = batch
        pair = jnp.minimum(r_user[u], r_item[i])
        h = hist[u]
        occ = jnp.where((h < n) & (pair > 0)[:, None], pair[:, None], 0)
        flops = (TRAIN_FLOPS_PER_FACTOR * jnp.sum(pair)
                 + IMPLICIT_FLOPS_PER_FACTOR * jnp.sum(occ))
        factors = (_row_max_sum(u, pair, k) + _row_max_sum(i, pair, k)
                   + _row_max_sum(h.reshape(-1), occ.reshape(-1), k))
        su = jnp.sort(u)
        first = jnp.concatenate([jnp.ones((1,), bool), su[1:] != su[:-1]])
        ids = jnp.sum(jnp.where(first, sizes[su], 0))
        nbytes = (ROW_BYTES_PER_FACTOR * factors + INDEX_BYTES * ids
                  + TRAIN_BYTES_PER_RATING * u.shape[0])
        return flops, nbytes

    return jax.lax.map(one, (users, items))
