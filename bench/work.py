"""Work that the configuration requires, counted at effective ranks.

Every count here comes from shapes and rank histograms, never from what an
implementation happens to execute: a change that skips pruned work is
credited, and a share of a peak computed from these counts cannot pass
100% unless the time leaves out part of the work.

A rank histogram ``h`` has ``k + 1`` bins: ``h[r]`` rows of effective rank r.
"""
from __future__ import annotations

import numpy as np

F32 = 4            # bytes per float32
INDEX_BYTES = 4    # int32 ids

# FLOPs of one training rating per factor of its pair rank r:
#   dot p.q over r factors                 2 (multiply, add)
#   gradients lam*p - err*q, lam*q - err*p  2 x 3
#   Adagrad on each of the two rows         2 x 7
#     (g*g, acc + g*g, + eps, sqrt, divide, times lr, add to the factor)
TRAIN_FLOPS_PER_FACTOR = 2 + 2 * 3 + 2 * 7
# bytes of one training rating per factor: read and write the two factor
# rows and the two Adagrad rows over the first r factors
TRAIN_BYTES_PER_FACTOR = 2 * 4 * F32
# per rating, independent of rank: user id, item id, rating
TRAIN_BYTES_PER_RATING = 2 * INDEX_BYTES + F32


def histogram(ranks, k: int) -> np.ndarray:
    return np.bincount(np.asarray(ranks).reshape(-1), minlength=k + 1)[: k + 1]


def train_flops(ratings: float, pair_rank: float) -> float:
    return ratings * pair_rank * TRAIN_FLOPS_PER_FACTOR


def train_bytes(ratings: float, pair_rank: float) -> float:
    return ratings * (pair_rank * TRAIN_BYTES_PER_FACTOR + TRAIN_BYTES_PER_RATING)


def score_flops(user_ranks: np.ndarray, h_item: np.ndarray) -> float:
    """2 * sum over the given users and every item of min(r_u, r_i)."""
    r = np.arange(len(h_item))
    # for each user rank a: sum_i min(a, r_i)
    per_rank = (np.minimum(r[:, None], r[None, :]) * h_item[None, :]).sum(1)
    return 2.0 * float(per_rank[np.asarray(user_ranks)].sum())


def score_bytes(user_ranks: np.ndarray, h_item: np.ndarray, topk: int) -> float:
    """One pass over the catalog at its effective ranks, the users' rows at
    theirs, and a (score, index) pair per result."""
    r = np.arange(len(h_item))
    catalog = float((r * h_item).sum()) * F32
    users = float(np.asarray(user_ranks).sum()) * F32
    out = len(np.asarray(user_ranks)) * topk * (F32 + INDEX_BYTES)
    return catalog + users + out


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time on the chip: the larger of the compute and memory bounds."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
