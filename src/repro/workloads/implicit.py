"""Confidence-weighted implicit-feedback MF (Hu/Koren/Volinsky, ICDM'08).

Implicit feedback gives no ratings — only observed interactions (clicks,
plays, purchases).  The WALS formulation trains on *binary preference*
``p_ui ∈ {0, 1}`` with a per-example *confidence* ``c_ui = 1 + alpha·r_ui``
(``r_ui`` = interaction strength; 1 for a bare click), minimizing

    sum_ui  c_ui · (p_ui - x_u·y_i)^2  +  lam·(||X||^2 + ||Y||^2).

That is exactly the weighted least-squares objective the existing stack
already speaks: the binary preference becomes the ``rating`` column and the
confidence becomes the ``batch["weight"]`` gate of ``mf.train_step`` —
the weight scales the update (and metrics), never the prediction, which is
precisely the WALS gradient ``c_ui·err·y_i``.  So the implicit objective
flows through ``train_epoch_scan`` and the ``OnlineUpdater`` *unchanged*;
this module only owns the data transformation (positives + sampled
negatives + confidence column).

Unobserved (user, item) pairs are weak negatives: preference 0 at the floor
confidence 1.  Training on every unobserved cell is O(m·n), so — as in
cuMF/implicit-ALS practice for SGD solvers — we sample ``negatives``
unobserved items per positive.
"""
from __future__ import annotations

from typing import Iterable, Iterator, Optional, Tuple

import numpy as np

from repro.data.ratings import RatingsDataset
from repro.online.stream import Event, EventBatch, iter_microbatches


def confidence_weights(ratings: np.ndarray, alpha: float) -> np.ndarray:
    """WALS confidence ``c = 1 + alpha·r`` for interaction strengths ``r``."""
    return (1.0 + alpha * np.asarray(ratings, np.float32)).astype(np.float32)


def _positive_sets(user: np.ndarray, item: np.ndarray, num_users: int):
    """Per-user sets of interacted items, for negative rejection."""
    sets = [set() for _ in range(num_users)]
    for u, i in zip(user, item):
        sets[u].add(int(i))
    return sets


def _sample_negatives(
    rng: np.random.Generator,
    users: np.ndarray,
    pos_sets,
    num_items: int,
    *,
    max_tries: int = 16,
) -> np.ndarray:
    """One uniformly-sampled unobserved item per row of ``users``.

    Rejection against the user's positive set, bounded at ``max_tries``
    draws per row (a user who interacted with the whole catalog keeps the
    last draw — a true negative does not exist for them).
    """
    neg = rng.integers(0, num_items, users.size).astype(np.int32)
    for _ in range(max_tries):
        clash = np.asarray(
            [int(n) in pos_sets[u] for u, n in zip(users, neg)], bool
        )
        if not clash.any():
            break
        neg[clash] = rng.integers(0, num_items, int(clash.sum()))
    return neg


def implicit_dataset(
    ds: RatingsDataset,
    *,
    alpha: float = 40.0,
    negatives: int = 4,
    seed: int = 0,
) -> Tuple[RatingsDataset, np.ndarray]:
    """Derive the WALS training set from an interaction log.

    Every interaction of ``ds`` becomes a positive example — preference
    (rating) 1 with confidence ``1 + alpha·r`` where ``r`` is the original
    rating column read as interaction strength — and each positive draws
    ``negatives`` sampled unobserved items at preference 0, confidence 1
    (the floor every unobserved cell carries in Hu et al.).

    Returns ``(binary_ds, confidence)``: a :class:`RatingsDataset` with
    ratings in {0, 1} on the same (num_users, num_items) geometry, plus the
    aligned confidence column to pass as ``pack_ratings(..., weight=...)``
    (or a batch's ``weight`` key).  Deterministic in ``seed``.
    """
    if negatives < 0:
        raise ValueError(f"negatives must be >= 0, got {negatives}")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    user = np.asarray(ds.user, np.int32)
    item = np.asarray(ds.item, np.int32)
    strength = np.asarray(ds.rating, np.float32)
    n = user.size

    pos_sets = _positive_sets(user, item, ds.num_users)
    users = [user]
    items = [item]
    ratings = [np.ones(n, np.float32)]
    weights = [confidence_weights(strength, alpha)]
    for _ in range(negatives):
        users.append(user)
        items.append(_sample_negatives(rng, user, pos_sets, ds.num_items))
        ratings.append(np.zeros(n, np.float32))
        weights.append(np.ones(n, np.float32))

    binary = RatingsDataset(
        user=np.concatenate(users),
        item=np.concatenate(items),
        rating=np.concatenate(ratings),
        num_users=ds.num_users,
        num_items=ds.num_items,
        rating_min=0.0,
        rating_max=1.0,
    )
    return binary, np.concatenate(weights)


def binarize_positives(ds: RatingsDataset) -> RatingsDataset:
    """Held-out positives as preference-1 examples (no negatives) — the
    eval-side counterpart of :func:`implicit_dataset`: test error becomes
    "how far from 1 does the model score the user's actual interactions"."""
    return RatingsDataset(
        user=np.asarray(ds.user, np.int32),
        item=np.asarray(ds.item, np.int32),
        rating=np.ones(len(ds), np.float32),
        num_users=ds.num_users,
        num_items=ds.num_items,
        rating_min=0.0,
        rating_max=1.0,
    )


def implicit_event_batch(
    batch: EventBatch,
    *,
    num_items: int,
    alpha: float = 40.0,
    negatives: int = 4,
    rng: Optional[np.random.Generator] = None,
) -> EventBatch:
    """Convert one click micro-batch into a WALS update batch.

    The streaming analogue of :func:`implicit_dataset`: each event becomes
    a preference-1 example at confidence ``1 + alpha·r`` (``r = 1`` when the
    batch is rating-free) plus ``negatives`` uniformly-sampled items at
    preference 0, confidence 1 — negatives reuse the event's user, so the
    update touches no rows serving has not already seen for this user.  The
    result always carries ratings and weights, so it feeds
    ``OnlineUpdater.apply`` directly.  If the incoming batch already has a
    recency ``weight`` column, it multiplies the confidence (both gate the
    update, so they compose multiplicatively).
    """
    if rng is None:
        rng = np.random.default_rng(0)
    n = len(batch)
    user = np.asarray(batch.user, np.int32)
    item = np.asarray(batch.item, np.int32)
    strength = (
        np.ones(n, np.float32) if batch.rating is None
        else np.asarray(batch.rating, np.float32)
    )
    conf = confidence_weights(strength, alpha)
    if batch.weight is not None:
        conf = conf * np.asarray(batch.weight, np.float32)

    users = [user]
    items = [item]
    ratings = [np.ones(n, np.float32)]
    weights = [conf]
    # per-batch positive rejection only: the stream owns no global catalog
    # view, so a negative is "not clicked in this batch by this user"
    seen = {(int(u), int(i)) for u, i in zip(user, item)}
    for _ in range(negatives):
        neg = rng.integers(0, num_items, n).astype(np.int32)
        for row in range(n):
            tries = 0
            while (int(user[row]), int(neg[row])) in seen and tries < 16:
                neg[row] = rng.integers(0, num_items)
                tries += 1
        users.append(user)
        items.append(neg)
        ratings.append(np.zeros(n, np.float32))
        weights.append(
            np.ones(n, np.float32) if batch.weight is None
            else np.asarray(batch.weight, np.float32)
        )
    return EventBatch(
        user=np.concatenate(users),
        item=np.concatenate(items),
        rating=np.concatenate(ratings),
        weight=np.concatenate(weights),
    )


def implicit_microbatches(
    source: Iterable[Event],
    batch_size: int,
    *,
    num_items: int,
    alpha: float = 40.0,
    negatives: int = 4,
    seed: int = 0,
    max_events: Optional[int] = None,
    half_life_s: Optional[float] = None,
) -> Iterator[EventBatch]:
    """Click stream → WALS update batches: :func:`iter_microbatches`
    composed with :func:`implicit_event_batch` (seeded, deterministic)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x11]))
    for batch in iter_microbatches(
        source, batch_size, max_events=max_events, half_life_s=half_life_s
    ):
        yield implicit_event_batch(
            batch, num_items=num_items, alpha=alpha,
            negatives=negatives, rng=rng,
        )


def strip_ratings(source: Iterable[Event]) -> Iterator[Event]:
    """View a rated stream as a rating-free click stream (``rating=None``)
    — what a click log looks like to the ranking-only prequential path."""
    for event in source:
        yield Event(event.user, event.item, None, event.timestamp)
