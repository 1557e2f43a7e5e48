"""Deterministic, resumable batch iteration — host-side and device-resident.

Shuffle order is a pure function of (seed, epoch), so a job restored from a
checkpoint at (epoch, step) replays the identical data order — the property
fault-tolerant restarts depend on (tests/test_checkpoint.py exercises it).
Batches are fixed-shape (pad-with-weight for eval, drop-remainder for train)
so a single compiled step serves the whole epoch.

Two data paths share these contracts:

* :func:`iterate_batches` — the legacy host loop: numpy slices yielded per
  step, uploaded by the caller.  Still the owner of mid-epoch resume
  (``start_step``) and of ad-hoc iteration.
* :class:`PackedRatings` / :func:`pack_eval_batches` — the epoch-compiled
  path: the ratings table is uploaded to the device ONCE at construction;
  each epoch draws a jitted on-device permutation (keyed on ``(seed,
  epoch)``, so it is exactly as deterministic as the host path, though the
  two orders differ) and reshapes into ``(steps, B)`` arrays that
  ``mf.train_epoch_scan`` folds over.  No per-step host→device uploads, no
  per-step dispatch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.ratings import RatingsDataset


def epoch_permutation(n: int, seed: int, epoch: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, epoch]))
    return rng.permutation(n)


def iterate_batches(
    ds: RatingsDataset,
    batch_size: int,
    *,
    seed: int = 0,
    epoch: int = 0,
    shuffle: bool = True,
    drop_remainder: bool = True,
    start_step: int = 0,
    hist: Optional[np.ndarray] = None,
) -> Iterator[Dict[str, np.ndarray]]:
    """Yield fixed-shape batches; resume mid-epoch with ``start_step``."""
    n = len(ds)
    order = epoch_permutation(n, seed, epoch) if shuffle else np.arange(n)
    num_full = n // batch_size
    steps = num_full if drop_remainder else -(-n // batch_size)
    for step in range(start_step, steps):
        idx = order[step * batch_size : (step + 1) * batch_size]
        weight = np.ones(batch_size, np.float32)
        if idx.shape[0] < batch_size:  # padded tail (eval only)
            pad = batch_size - idx.shape[0]
            weight[idx.shape[0]:] = 0.0
            idx = np.concatenate([idx, np.zeros(pad, idx.dtype)])
        batch = {
            "user": ds.user[idx],
            "item": ds.item[idx],
            "rating": ds.rating[idx],
        }
        if not drop_remainder:
            # train batches (drop_remainder) are always full: omitting the
            # all-ones weight keeps train_step's weight-free fast path
            batch["weight"] = weight
        if hist is not None:
            batch["hist"] = hist[ds.user[idx]]
        yield batch


def num_steps(ds: RatingsDataset, batch_size: int, drop_remainder: bool = True) -> int:
    n = len(ds)
    return n // batch_size if drop_remainder else -(-n // batch_size)


# ---------------------------------------------------------------------------
# Device-resident packed epochs (the train_epoch_scan data path)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("steps", "batch_size", "shuffle"))
def _permute_and_batch(
    user: jax.Array,
    item: jax.Array,
    rating: jax.Array,
    base_key: jax.Array,
    epoch: jax.Array,
    weight: Optional[jax.Array] = None,
    *,
    steps: int,
    batch_size: int,
    shuffle: bool,
) -> Dict[str, jax.Array]:
    n = user.shape[0]
    if shuffle:
        # fold_in runs inside the jit so the per-epoch key derivation never
        # leaves the device; only the 4-byte epoch scalar crosses the host
        # boundary per epoch (and that via an explicit device_put)
        take = jax.random.permutation(jax.random.fold_in(base_key, epoch), n)[
            : steps * batch_size
        ]
    else:
        take = jnp.arange(steps * batch_size, dtype=jnp.int32)

    def gather(x):
        return x[take].reshape(steps, batch_size)

    out = {"user": gather(user), "item": gather(item), "rating": gather(rating)}
    if weight is not None:
        out["weight"] = gather(weight)
    return out


@dataclasses.dataclass(frozen=True)
class PackedRatings:
    """A ratings table uploaded to the device once, reshuffled on-device.

    ``epoch_batches(seed, epoch)`` returns ``{"user", "item", "rating"}``
    arrays shaped ``(steps, batch_size)`` — the operand of
    ``mf.train_epoch_scan``.  The permutation is a jitted
    ``jax.random.permutation`` keyed on ``fold_in(seed, epoch)``:
    deterministic per (seed, epoch), so checkpoint restarts replay the
    identical order, and no bytes cross the host boundary after
    construction.  Train semantics (drop-remainder) only; eval packing is
    :func:`pack_eval_batches`.
    """

    user: jax.Array     # (N,) int32, device-resident
    item: jax.Array     # (N,) int32
    rating: jax.Array   # (N,) float32
    batch_size: int
    # optional per-example importance weights (confidence weighting for the
    # implicit objective): shuffled alongside and emitted as the batches'
    # "weight" column, which train_step's update gate consumes
    weight: Optional[jax.Array] = None   # (N,) float32
    # per-seed base PRNG keys, uploaded once and reused every epoch so the
    # reshuffle stays device-resident (no hidden host round-trips); cache
    # state, not identity — excluded from eq/repr of the frozen dataclass
    _base_keys: Dict[int, jax.Array] = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )

    @property
    def num_examples(self) -> int:
        return int(self.user.shape[0])

    @property
    def num_steps(self) -> int:
        return self.num_examples // self.batch_size

    def epoch_batches(
        self, seed: int, epoch: int, *, shuffle: bool = True
    ) -> Dict[str, jax.Array]:
        if self.num_steps == 0:
            raise ValueError(
                f"batch_size {self.batch_size} exceeds the dataset "
                f"({self.num_examples} ratings)"
            )
        base = self._base_keys.get(seed)
        if base is None:
            base = self._base_keys.setdefault(
                seed, jax.device_put(jax.random.PRNGKey(seed))
            )
        return _permute_and_batch(
            self.user, self.item, self.rating, base,
            jax.device_put(np.uint32(epoch)), self.weight,
            steps=self.num_steps, batch_size=self.batch_size, shuffle=shuffle,
        )


def pack_ratings(
    ds: RatingsDataset,
    batch_size: int,
    *,
    weight: Optional[np.ndarray] = None,
) -> PackedRatings:
    """Upload the ratings table once; see :class:`PackedRatings`.

    ``weight`` attaches per-example importance weights (e.g. the implicit
    objective's confidence column) that ride through the epoch shuffle into
    each batch's ``weight`` gate.
    """
    if weight is not None and weight.shape[0] != len(ds):
        raise ValueError(
            f"weight length {weight.shape[0]} != dataset size {len(ds)}"
        )
    return PackedRatings(
        user=jnp.asarray(ds.user, jnp.int32),
        item=jnp.asarray(ds.item, jnp.int32),
        rating=jnp.asarray(ds.rating, jnp.float32),
        batch_size=int(batch_size),
        weight=None if weight is None else jnp.asarray(weight, jnp.float32),
    )


def pack_eval_batches(
    ds: RatingsDataset, batch_size: int
) -> Dict[str, jax.Array]:
    """Pre-packed ``(steps, B)`` eval batches, built and uploaded once.

    Deterministic order, padded tail carried by a zero ``weight`` column —
    the operand of ``mf.eval_epoch_scan`` (SVD++ histories are gathered on
    device inside the scan, not packed here).
    """
    n = len(ds)
    batch_size = min(batch_size, max(n, 1))
    steps = -(-n // batch_size)
    pad = steps * batch_size - n
    idx = np.concatenate([np.arange(n), np.zeros(pad, np.int64)])
    weight = np.concatenate(
        [np.ones(n, np.float32), np.zeros(pad, np.float32)]
    )
    return {
        "user": jnp.asarray(ds.user[idx].reshape(steps, batch_size)),
        "item": jnp.asarray(ds.item[idx].reshape(steps, batch_size)),
        "rating": jnp.asarray(ds.rating[idx].reshape(steps, batch_size)),
        "weight": jnp.asarray(weight.reshape(steps, batch_size)),
    }
