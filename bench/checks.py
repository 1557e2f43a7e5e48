"""The numbers that decide ``correct``: gaps between what the timed path
produced and the plain reference, each later held to its cell's limit
(``bench/limits/<cell>.json``)."""
from __future__ import annotations

import numpy as np

from bench import reference

BLOCK = 128   # users per reference block


def _leaf_gap(prog: dict, ref: dict) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of that
    leaf's reference norm and the median leaf's."""
    median = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], median) for k in ref)


def train_gaps(prog: dict, ref: dict) -> dict:
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    return {
        "loss_gap": loss,
        "grad_gap": _leaf_gap(prog["grad"], ref["grad"]),
        "change_gap": _leaf_gap(prog["change"], ref["change"]),
    }


def reference_blocks(tables, users, items, *, topk: int, precision: str):
    """Reference readings for ``users`` in fixed-size blocks (the last one
    padded), so that every run reuses one compiled shape."""
    p, q, t_p, t_q = tables
    n = len(users)
    pad = (-n) % BLOCK
    users = np.concatenate([users, np.repeat(users[-1:], pad)])
    items = np.concatenate([items, np.repeat(items[-1:], pad, axis=0)])
    parts = []
    for lo in range(0, len(users), BLOCK):
        out = reference.score_block(
            p[users[lo:lo + BLOCK]], q, t_p, t_q, items[lo:lo + BLOCK],
            topk=topk, precision=precision,
        )
        parts.append([np.asarray(x) for x in out])
    picked, kth, scale, top_s, top_i = (np.concatenate(x)[:n] for x in zip(*parts))
    return picked, kth, scale, top_s, top_i


def topk_gaps(tables, users, served_s, served_i, *, topk: int) -> dict:
    """``answer_gap``: over every served (user, item), the larger of the gap
    between the served score and the reference's score of that item, and
    the shortfall of that reference score below the reference's k-th best
    -- a wrong score, a wrong item or a missed better item all read here.
    In units of the user's scale, the largest sum_t |p_t q_t| over its
    served items (a float32 dot is within k * 2**-24 of it)."""
    if len(users) == 0:
        return {"answer_gap": float("inf")}
    picked, kth, scale, _, _ = reference_blocks(
        tables, np.asarray(users, np.int32), np.asarray(served_i, np.int32),
        topk=topk, precision="highest",
    )
    return {"answer_gap": answer_gap(served_s, picked, kth, scale)}


def answer_gap(served_s, picked, kth, scale) -> float:
    gap = np.maximum(np.abs(np.asarray(served_s, np.float64) - picked),
                     np.maximum(kth[:, None] - picked, 0.0))
    s_u = scale.max(axis=1, keepdims=True)
    with np.errstate(divide="ignore", invalid="ignore"):
        return float(np.where(gap == 0, 0.0, gap / s_u).max())
