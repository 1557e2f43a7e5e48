"""Work an implicit-ALS epoch requires, counted at effective ranks from the
benchmark's own merged pairs (``bench/reference_ials.py``), never from what
the program executes.

With r_u the rank of user u, r_i that of item i, and a pair's rank
min(r_u, r_i), an epoch's two half-steps require:

* the Gramians: for each pair and side, its fixed row's outer product over
  the pair's rank, 2 min(r_u, r_i)^2 FLOPs, reading that row's first
  min(r_u, r_i) factors and the pair's id and confidence; and each side's
  fixed table Y~'Y~, 2 r^2 FLOPs and r factors read a fixed row;
* the solves: a Cholesky factorisation of r^3 / 3 FLOPs a solved row (a row
  with pairs), at the row's rank.

An implementation that skips pruned work is credited, and a share of a
peak computed from these counts cannot pass 100% unless the time leaves
out part of the work.
"""
from __future__ import annotations

import numpy as np

from bench.work import F32, INDEX_BYTES


def epoch_counts(user, item, r_user, r_item) -> dict:
    """FLOPs and bytes of one epoch over the pairs (``user``, ``item``) at
    the ranks ``r_user`` and ``r_item``: ``gram_flops``, ``gram_bytes``,
    ``solve_flops``."""
    r_user = np.asarray(r_user, np.int64)
    r_item = np.asarray(r_item, np.int64)
    pair = np.minimum(r_user[user], r_item[item])
    solved_u = r_user[np.unique(user)]
    solved_i = r_item[np.unique(item)]
    gram_flops = (2 * 2 * np.sum(pair ** 2)
                  + 2 * np.sum(r_item ** 2) + 2 * np.sum(r_user ** 2))
    gram_bytes = (2 * (F32 * np.sum(pair) + (INDEX_BYTES + F32) * pair.size)
                  + F32 * (np.sum(r_item) + np.sum(r_user)))
    solve_flops = (np.sum(solved_u ** 3) + np.sum(solved_i ** 3)) / 3.0
    return {"gram_flops": float(gram_flops), "gram_bytes": float(gram_bytes),
            "solve_flops": float(solve_flops)}
