"""Incremental pruned factor updates — the paper's Alg. 2/3 applied online.

The trainer (``core/trainer.py``) exercises the dynamically-pruned update
only in offline epochs; here the same masked update (``mf.train_step`` with
the trained thresholds, through any :class:`~repro.optim.optimizers.
RowOptimizer`) is applied to *streaming* event micro-batches.  Each batch
touches only its gathered rows of P/Q (plus biases / implicit rows), and the
early-stopping mask gates the per-row work exactly as in training — the
pruned incremental step does ``work_fraction < 1`` of the dense MACs.

Beyond the step itself the updater owns the three maintenance jobs a
long-running stream needs:

* **cold start** — events naming a user/item id past the current tables grow
  P/Q (and biases, implicit factors, optimizer state, histories) with
  freshly initialized rows, so the catalog follows the stream;
* **threshold drift** — the serving thresholds were calibrated against the
  factor distribution at training time; as online updates move (mu, sigma),
  :meth:`maybe_recalibrate` re-solves Eq. 7/8 and, past ``drift_budget``,
  adopts the new thresholds and re-runs the joint-sparsity rearrangement
  (§4.3) — permuting P, Q, implicit factors AND optimizer accumulators with
  one latent permutation so every inner product is preserved;
* **publish bookkeeping** — touched row sets and a ``layout_dirty`` flag,
  consumed by :class:`~repro.online.publisher.SnapshotPublisher` to drive
  the engine's touched-rows-only hot swap vs. a full layout rebuild.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mf, rearrange, threshold
from repro.data import loader
from repro.online.stream import EventBatch, RatingFreeStreamError
from repro.optim.optimizers import RowOptimizer


@dataclasses.dataclass
class PublishSnapshot:
    """What one :meth:`OnlineUpdater.snapshot` hands the publisher."""

    params: mf.MFParams
    t_p: jnp.ndarray
    t_q: jnp.ndarray
    touched_users: np.ndarray
    touched_items: np.ndarray
    touched_implicit_items: np.ndarray
    user_history: Optional[np.ndarray]
    full_rebuild: bool          # thresholds/permutation/geometry changed
    events_seen: int            # cumulative over the updater's lifetime
    snapshot_id: int = 0        # monotonic per updater; publisher/bus audit
    user_remap: Optional[np.ndarray] = None  # ext->phys (store/eviction.py)
    remap_epoch: int = 0        # compaction counter; bump => full heal


class OnlineUpdater:
    """Apply streaming event micro-batches as pruned row updates.

    ``batch_size`` caps a compiled step: event batches split into
    power-of-two chunks (so the jit cache stays bounded, as in serving —
    see :meth:`_chunk_sizes`).  ``pruning_rate``
    (needed only for drift recalibration) defaults to the rate implied by
    nothing — pass the training rate to enable :meth:`maybe_recalibrate`.
    """

    def __init__(
        self,
        params: mf.MFParams,
        opt_state: Optional[mf.MFOptState] = None,
        t_p=0.0,
        t_q=0.0,
        *,
        optimizer: str | RowOptimizer = "adagrad",
        lr: float = 0.05,
        lam: float = 0.02,
        pruning_rate: float = 0.0,
        drift_budget: float = 0.25,
        user_history: Optional[np.ndarray] = None,
        batch_size: int = 256,
        init_scale: float = 0.1,
        seed: int = 0,
        mesh=None,
        grad_compression: str = "none",
    ):
        self.opt = (
            optimizer if isinstance(optimizer, RowOptimizer)
            else RowOptimizer(name=optimizer)
        )
        self.params = params
        self.opt_state = (
            opt_state if opt_state is not None
            else mf.init_opt_state(params, self.opt)
        )
        self.mesh = mesh
        self._user_multiple = self._item_multiple = 1
        if mesh is not None:
            # Distributed refresh: event batches route through the owner-
            # compute train_step_shard_map (ROADMAP "distributed online
            # updates").  Only the FunkSVD variants the sharded step
            # implements are eligible.
            if self.opt.name not in ("sgd", "adagrad"):
                raise ValueError(
                    "mesh-backed online updates support sgd/adagrad only "
                    f"(got {self.opt.name!r})"
                )
            if params.user_bias is not None or params.implicit is not None:
                raise ValueError(
                    "mesh-backed online updates support the FunkSVD variant "
                    "only (no biases / implicit factors)"
                )
            self._n_dp = 1
            for axis in ("pod", "data"):
                if axis in mesh.axis_names:
                    self._n_dp *= mesh.shape[axis]
            self._user_multiple = self._n_dp
            self._item_multiple = mesh.shape["model"]
            if (
                params.p.shape[0] % self._user_multiple
                or params.q.shape[0] % self._item_multiple
            ):
                raise ValueError(
                    "factor tables must divide over the mesh: "
                    f"P rows {params.p.shape[0]} over {self._user_multiple}, "
                    f"Q rows {params.q.shape[0]} over {self._item_multiple}"
                )
            if grad_compression == "int8_ef":
                # per-sender quantization residuals ride in the opt_state
                # (row-indexed, so capacity growth keeps them aligned)
                self.opt_state = mf.init_error_feedback_state(
                    params, self.opt_state, mesh
                )
            self._sharded_step = jax.jit(
                functools.partial(
                    mf.train_step_shard_map,
                    lr=float(lr), lam=float(lam), opt_name=self.opt.name,
                    grad_compression=grad_compression, mesh=mesh,
                )
            )
        self.t_p = jnp.asarray(t_p, jnp.float32)
        self.t_q = jnp.asarray(t_q, jnp.float32)
        self.lr = jnp.float32(lr)
        self.lam = float(lam)
        self.pruning_rate = float(pruning_rate)
        self.drift_budget = float(drift_budget)
        self.batch_size = int(batch_size)
        self.init_scale = float(init_scale)
        self._rng = np.random.default_rng(seed)
        if params.implicit is not None and user_history is None:
            raise ValueError(
                "SVD++ params need user_history (data.build_user_history) so "
                "online events can extend the implicit-feedback sets"
            )
        self.user_history = (
            None if user_history is None
            else np.array(user_history, np.int32, copy=True)
        )
        # |N(u)| of each user as built: the history capacity of a chunk
        # size (mf.implicit_capacity) is fixed from these, so a chunk size
        # compiles one step however the histories fill
        self._history_sizes = (
            None if user_history is None
            else np.sum(self.user_history < params.q.shape[0], axis=1)
        )
        self._capacities: Dict[int, int] = {}
        self._dim_mask = jnp.ones((params.p.shape[1],), jnp.float32)
        self.evictor = None  # store.eviction.UserEvictor via attach_evictor

        # publish bookkeeping
        self._touched_users: Set[int] = set()
        self._touched_items: Set[int] = set()
        self._touched_implicit: Set[int] = set()
        self._layout_dirty = False
        self.events_seen = 0
        self.snapshots_taken = 0
        self.batches_applied = 0
        self._work_sum = 0.0
        self._abs_err_sum = 0.0

    # -- construction --------------------------------------------------------
    @classmethod
    def from_trainer(cls, trainer, **kwargs) -> "OnlineUpdater":
        """Continue a :class:`~repro.core.trainer.DPMFTrainer` run online:
        same params, optimizer state, thresholds, and history."""
        cfg = trainer.config
        kwargs.setdefault("optimizer", trainer.opt)
        kwargs.setdefault("lr", cfg.lr)
        kwargs.setdefault("lam", cfg.lam)
        kwargs.setdefault("pruning_rate", cfg.pruning_rate)
        kwargs.setdefault("user_history", trainer.hist)
        kwargs.setdefault("batch_size", min(cfg.batch_size, 4096))
        kwargs.setdefault("grad_compression", cfg.grad_compression)
        return cls(
            trainer.params, trainer.opt_state, trainer.t_p, trainer.t_q,
            **kwargs,
        )

    def attach_evictor(self, evictor) -> None:
        """Arm cold-row eviction (``store/eviction.UserEvictor``): event
        user ids become *external* ids, translated to physical rows on
        every apply; ``evictor.maybe_evict()`` may spill + compact the user
        tables at publish points."""
        evictor.bind(self)
        self.evictor = evictor

    def resolve_users(self, users: np.ndarray) -> np.ndarray:
        """External user ids → physical rows for an *update* (grows /
        revives as needed).  Identity + cold-start growth when no evictor
        is attached — the prequential evaluator and other scorers call this
        instead of ``ensure_capacity`` so they stay remap-correct."""
        users = np.asarray(users, np.int32)
        if users.size == 0:
            return users
        if self.evictor is None:
            self.ensure_capacity(int(users.max()), -1)
            return users
        return self.evictor.resolve(users).astype(np.int32)

    # -- properties ----------------------------------------------------------
    @property
    def num_users(self) -> int:
        """Current user-table rows (grows with cold-start events)."""
        return self.params.p.shape[0]

    @property
    def num_items(self) -> int:
        """Current catalog size (grows with cold-start events)."""
        return self.params.q.shape[0]

    @property
    def mean_work_fraction(self) -> float:
        """Mean executed share of dense MACs over the updater's lifetime —
        the online analogue of the trainer's per-epoch work_fraction."""
        return self._work_sum / max(self.batches_applied, 1)

    @property
    def mean_abs_err(self) -> float:
        """Mean per-batch training |error| over the updater's lifetime — the
        streaming analogue of the trainer's per-epoch train_abs_err."""
        return self._abs_err_sum / max(self.batches_applied, 1)

    # -- cold start ----------------------------------------------------------
    def _fresh_rows(self, rows: int, k: int, dtype) -> jnp.ndarray:
        return jnp.asarray(
            self.init_scale * self._rng.standard_normal((rows, k)),
            dtype,
        )

    def _grow_state(self, state: Dict, rows: int, axis0: int) -> Dict:
        def grow(v):
            if getattr(v, "ndim", 0) >= 1 and v.shape[0] == axis0:
                pad = [(0, rows)] + [(0, 0)] * (v.ndim - 1)
                return jnp.pad(v, pad)
            return v

        return {key: grow(value) for key, value in state.items()}

    def ensure_capacity(self, max_user: int, max_item: int) -> bool:
        """Grow the factor tables so ``max_user``/``max_item`` are valid ids.

        New rows get the training init (``init_scale * N(0, 1)``) so pruning
        thresholds remain meaningful; optimizer accumulators start at zero;
        new SVD++ history rows start empty (all padding).  Returns True if
        anything grew.  Growth only ever appends — live request ids stay
        valid (the engine's swap enforces the same).
        """
        params, grew = self.params, False
        m, k = params.p.shape
        n = params.q.shape[0]

        # In mesh mode, growth rounds up to the mesh multiples so the grown
        # tables keep dividing over the data/model axes.
        def round_up(v: int, mult: int) -> int:
            return -(-v // mult) * mult

        add_n = max(0, round_up(max_item + 1, self._item_multiple) - n)
        add_m = max(0, round_up(max_user + 1, self._user_multiple) - m)
        if self.mesh is not None and (add_n or add_m):
            # Gather the sharded tables to replicated host arrays before
            # growing: jnp.concatenate of a mesh-sharded table with fresh
            # rows re-shards the longer result and (jax 0.4.x) scrambles the
            # existing rows.  The next sharded step re-shards its inputs
            # anyway, exactly like the first step after construction.
            def unshard(tree):
                return jax.tree_util.tree_map(
                    lambda x: jnp.asarray(np.asarray(x)), tree
                )

            params = unshard(params)
            self.opt_state = unshard(self.opt_state)
        if add_n:
            grew = True
            new_n = n + add_n
            params = params._replace(
                q=jnp.concatenate([params.q, self._fresh_rows(add_n, k, params.q.dtype)]),
                item_bias=(
                    None if params.item_bias is None
                    else jnp.pad(params.item_bias, ((0, add_n), (0, 0)))
                ),
            )
            if params.implicit is not None:
                # (n + 1, k) with the inert padding row LAST: old rows, fresh
                # rows, then a new zero padding row at index new_n
                params = params._replace(
                    implicit=jnp.concatenate([
                        params.implicit[:n],
                        self._fresh_rows(add_n, k, params.implicit.dtype),
                        jnp.zeros((1, k), params.implicit.dtype),
                    ])
                )
                if self.user_history is not None:
                    # remap the old padding sentinel to the new one
                    self.user_history[self.user_history == n] = new_n
            self.opt_state = self.opt_state._replace(
                q=self._grow_state(self.opt_state.q, add_n, n),
                item_bias=(
                    None if self.opt_state.item_bias is None
                    else self._grow_state(self.opt_state.item_bias, add_n, n)
                ),
                implicit=(
                    None if self.opt_state.implicit is None
                    else {
                        key: jnp.concatenate(
                            [v[:n], jnp.zeros((add_n,) + v.shape[1:], v.dtype), v[n:]]
                        )
                        if getattr(v, "ndim", 0) >= 1 and v.shape[0] == n + 1
                        else v
                        for key, v in self.opt_state.implicit.items()
                    }
                ),
            )
            self._touched_items.update(range(n, new_n))
            self._touched_implicit.update(range(n, new_n))
            n = new_n

        if add_m:
            grew = True
            params = params._replace(
                p=jnp.concatenate([params.p, self._fresh_rows(add_m, k, params.p.dtype)]),
                user_bias=(
                    None if params.user_bias is None
                    else jnp.pad(params.user_bias, ((0, add_m), (0, 0)))
                ),
            )
            self.opt_state = self.opt_state._replace(
                p=self._grow_state(self.opt_state.p, add_m, m),
                user_bias=(
                    None if self.opt_state.user_bias is None
                    else self._grow_state(self.opt_state.user_bias, add_m, m)
                ),
            )
            if self.user_history is not None:
                self.user_history = np.concatenate([
                    self.user_history,
                    np.full((add_m, self.user_history.shape[1]), n, np.int32),
                ])
            self._touched_users.update(range(m, m + add_m))

        if grew:
            # Growth does NOT mark the layout dirty: the engine's swap
            # detects a changed catalog geometry on its own (and rebuilds),
            # user-only growth patches incrementally, and grown rows are all
            # in the touched sets so a row delta still describes the change.
            self.params = params
        return grew

    # -- the incremental step ------------------------------------------------
    def _append_history(self, users: np.ndarray, items: np.ndarray) -> None:
        """Record new interactions in the SVD++ implicit sets: first free
        slot, or FIFO eviction of the oldest entry when the bounded history
        is full (slots fill left to right, so slot 0 is oldest) — fresh
        interactions always make it into the implicit set."""
        hist = self.user_history
        pad = self.num_items
        for u, i in zip(users, items):
            row = hist[u]
            if i in row:
                continue
            free = np.nonzero(row == pad)[0]
            if free.size:
                row[free[0]] = i
            else:
                row[:-1] = row[1:]
                row[-1] = i

    @staticmethod
    def _chunk_sizes(total: int, cap: int):
        """Binary decomposition of ``total`` into power-of-two chunk sizes
        (capped at ``cap``): jit sees only O(log cap) distinct batch shapes,
        and — unlike zero-weight padding — no row is ever duplicated, so the
        stateful optimizers (momentum/adadelta/adam), whose duplicate-index
        scatter write-back is nondeterministic and whose state decays even
        for zero-weight rows, stay exact too."""
        sizes = []
        while total >= cap:
            sizes.append(cap)
            total -= cap
        bit = 1
        while total:
            if total & bit:
                sizes.append(bit)
                total &= ~bit
            bit <<= 1
        sizes.sort(reverse=True)
        return sizes

    def apply(self, batch: EventBatch) -> Dict[str, float]:
        """Apply one event micro-batch; returns step metrics.

        The batch is split into power-of-two chunks (largest first, capped
        at ``batch_size``) so the compiled-step cache stays bounded without
        any padding rows.  ``work_fraction`` is the executed share of dense
        MACs over the real events — the online analogue of the trainer's
        per-epoch number.
        """
        if len(batch) == 0:
            return {"abs_err": 0.0, "work_fraction": 1.0, "events": 0}
        if batch.rating is None:
            raise RatingFreeStreamError(
                "OnlineUpdater.apply trains on the rating column and this "
                "batch is rating-free.  Convert clicks into weighted binary "
                "preferences first — repro.workloads.implicit."
                "implicit_event_batch(batch, num_items=...) — then apply "
                "the converted batch."
            )
        users = np.asarray(batch.user, np.int32)
        items = np.asarray(batch.item, np.int32)
        ratings = np.asarray(batch.rating, np.float32)
        weights = (
            None if getattr(batch, "weight", None) is None
            else np.asarray(batch.weight, np.float32)
        )
        if self.evictor is not None:
            # external ids -> physical rows (reviving spilled users); from
            # here on every array/bookkeeping index is physical
            users = self.evictor.resolve(users)
        self.ensure_capacity(int(users.max()), int(items.max()))
        if self.user_history is not None:
            self._append_history(users, items)

        total = len(users)
        if self.mesh is not None:
            # Distributed refresh: one owner-compute sharded step per event
            # batch.  The router buckets rows by user owner and pads with
            # weight-0 rows (pow2 lengths keep the jit cache bounded).
            from repro.distributed.sharding import route_batch_to_owner_shards

            routed = route_batch_to_owner_shards(
                users, items, ratings,
                num_users=self.num_users, n_dp=self._n_dp,
                weight=weights, pad_to_pow2=True,
            )
            step_batch = {key: jnp.asarray(v) for key, v in routed.items()}
            self.params, self.opt_state, metrics = self._sharded_step(
                self.params, self.opt_state, step_batch, self.t_p, self.t_q
            )
            abs_err = float(metrics["abs_err"]) * total
            work = float(metrics["work_fraction"]) * total
        else:
            abs_err = work = 0.0
            lo = 0
            for size in self._chunk_sizes(total, self.batch_size):
                u = users[lo : lo + size]
                i = items[lo : lo + size]
                r = ratings[lo : lo + size]
                step_batch = {
                    "user": jnp.asarray(u),
                    "item": jnp.asarray(i),
                    "rating": jnp.asarray(r),
                }
                if weights is not None:
                    step_batch["weight"] = jnp.asarray(weights[lo : lo + size])
                lo += size
                capacity = None
                if self.user_history is not None:
                    step_batch["hist"] = jnp.asarray(self.user_history[u])
                    capacity = self._implicit_capacity(size)
                self.params, self.opt_state, metrics = mf.train_step(
                    self.params, self.opt_state, step_batch,
                    self.t_p, self.t_q, self.lr, self._dim_mask,
                    opt=self.opt, lam=self.lam, capacity=capacity,
                )
                abs_err += float(metrics["abs_err"]) * size
                work += float(metrics["work_fraction"]) * size

        self._touched_users.update(int(x) for x in users)
        self._touched_items.update(int(x) for x in items)
        if self.params.implicit is not None:
            # train_step updates the implicit rows of every history item of
            # the batch users — all of them are now stale for serving caches
            hist_rows = self.user_history[users]
            live = hist_rows[hist_rows < self.num_items]
            self._touched_implicit.update(int(x) for x in live)
        self.events_seen += total
        self.batches_applied += 1
        self._work_sum += work / total
        self._abs_err_sum += abs_err / total
        return {
            "abs_err": abs_err / total,
            "work_fraction": work / total,
            "events": total,
        }

    def _implicit_capacity(self, size: int) -> int:
        """The history slots a chunk of ``size`` events runs Y's side on,
        from the users' |N(u)| when the updater was built; a step whose
        histories have filled past it trains on all of its slots."""
        if size not in self._capacities:
            self._capacities[size] = mf.implicit_capacity(
                self._history_sizes, size, self.user_history.shape[1]
            )
        return self._capacities[size]

    # -- threshold drift maintenance -----------------------------------------
    def _candidate_thresholds(self):
        """(cand_p, cand_q, drift): thresholds the CURRENT factor
        distribution implies, plus their relative distance from the live
        ones.  One (mu, sigma) solve — drift() and maybe_recalibrate()
        share it rather than re-deriving."""
        cand_p, cand_q = threshold.thresholds_from_matrices(
            self.params.p, self.params.q, self.pruning_rate
        )
        ref_p = max(float(self.t_p), 1e-8)
        ref_q = max(float(self.t_q), 1e-8)
        drift = max(
            abs(float(cand_p) - float(self.t_p)) / ref_p,
            abs(float(cand_q) - float(self.t_q)) / ref_q,
        )
        return cand_p, cand_q, drift

    def drift(self) -> float:
        """Relative distance between the live thresholds and the ones the
        current factor distribution implies (0 when pruning is off)."""
        if self.pruning_rate <= 0.0:
            return 0.0
        return self._candidate_thresholds()[2]

    def maybe_recalibrate(self, *, force: bool = False) -> Optional[Dict]:
        """Re-measure (mu, sigma), and when drift exceeds ``drift_budget``
        adopt fresh thresholds and re-run the §4.3 rearrangement.

        The latent permutation is applied to P, Q, the implicit factors AND
        every 2-D optimizer accumulator — one permutation, every inner
        product preserved (the same discipline as ``DPMFTrainer.calibrate``).
        Marks the snapshot ``layout_dirty``: the engine must rebuild its
        catalog layouts, since both the masks (new t_q) and the latent order
        changed.  Returns a report dict, or None if within budget.
        """
        if self.pruning_rate <= 0.0:
            return None
        cand_p, cand_q, drift = self._candidate_thresholds()
        if not force and drift <= self.drift_budget:
            return None
        old_t_p, old_t_q = float(self.t_p), float(self.t_q)
        self.t_p, self.t_q = cand_p, cand_q
        result = rearrange.rearrangement(
            self.params.p, self.params.q, self.t_p, self.t_q
        )
        perm = result.perm
        new_p, new_q = rearrange.apply_perm(
            self.params.p, self.params.q, perm
        )
        self.params = self.params._replace(p=new_p, q=new_q)
        if self.params.implicit is not None:
            self.params = self.params._replace(
                implicit=jnp.take(self.params.implicit, perm, axis=1)
            )
        k = self.params.p.shape[1]

        def permute_state(state):
            if state is None:
                return None
            return {
                key: (
                    jnp.take(value, perm, axis=1)
                    if getattr(value, "ndim", 0) == 2 and value.shape[1] == k
                    else value
                )
                for key, value in state.items()
            }

        self.opt_state = self.opt_state._replace(
            p=permute_state(self.opt_state.p),
            q=permute_state(self.opt_state.q),
            implicit=permute_state(self.opt_state.implicit),
        )
        self._layout_dirty = True
        return {
            "drift": drift,
            "t_p": (old_t_p, float(self.t_p)),
            "t_q": (old_t_q, float(self.t_q)),
            "perm": np.asarray(perm),
        }

    # -- publishing ----------------------------------------------------------
    def snapshot(self) -> PublishSnapshot:
        """Freeze the accumulated delta for publication and reset the
        touched-row bookkeeping.  The history matrix is copied so the
        updater can keep appending while the engine serves the snapshot."""
        self.snapshots_taken += 1
        snap = PublishSnapshot(
            params=self.params,
            t_p=self.t_p,
            t_q=self.t_q,
            touched_users=np.fromiter(
                sorted(self._touched_users), np.int64,
                len(self._touched_users),
            ),
            touched_items=np.fromiter(
                sorted(self._touched_items), np.int64,
                len(self._touched_items),
            ),
            touched_implicit_items=np.fromiter(
                sorted(self._touched_implicit), np.int64,
                len(self._touched_implicit),
            ),
            user_history=(
                None if self.user_history is None
                else self.user_history.copy()
            ),
            full_rebuild=self._layout_dirty,
            events_seen=self.events_seen,
            snapshot_id=self.snapshots_taken,
            user_remap=(
                None if self.evictor is None
                else self.evictor.remap.as_array()
            ),
            remap_epoch=(
                0 if self.evictor is None else self.evictor.remap.epoch
            ),
        )
        self._touched_users.clear()
        self._touched_items.clear()
        self._touched_implicit.clear()
        self._layout_dirty = False
        return snap

    # -- evaluation ----------------------------------------------------------
    def evaluate(self, ds, batch_size: int = 8192) -> float:
        """Test MAE (Eq. 12) of the current online params + thresholds.

        With an evictor attached the dataset's user ids are *external*:
        live users score through their physical rows, spilled/unseen users
        score bias-only (global mean + item bias when the bias variant is
        trained, else 0) — the same fallback contract the serving engine
        applies.  Evaluation never revives rows.
        """
        if self.evictor is not None:
            return self._evaluate_remapped(ds, batch_size)
        total, count = 0.0, 0.0
        for batch_np in loader.iterate_batches(
            ds, min(batch_size, max(len(ds), 1)), shuffle=False,
            drop_remainder=False, hist=self.user_history,
        ):
            batch = {key: jnp.asarray(val) for key, val in batch_np.items()}
            s, c = mf.eval_mae(self.params, batch, self.t_p, self.t_q)
            total += float(s)
            count += float(c)
        return total / max(count, 1.0)

    def _evaluate_remapped(self, ds, batch_size: int) -> float:
        remap = self.evictor.remap
        total, count = 0.0, 0.0
        for batch_np in loader.iterate_batches(
            ds, min(batch_size, max(len(ds), 1)), shuffle=False,
            drop_remainder=False,
        ):
            users = np.asarray(batch_np["user"], np.int64)
            items = np.asarray(batch_np["item"], np.int64)
            phys = remap.lookup(users)
            live = phys >= 0
            safe = np.where(live, phys, 0).astype(np.int32)
            pred, _ = mf.predict_pairs(
                self.params, jnp.asarray(safe),
                jnp.asarray(items.astype(np.int32)), self.t_p, self.t_q,
            )
            pred = np.asarray(pred, np.float64)
            fallback = np.zeros(users.shape, np.float64)
            if self.params.global_mean is not None:
                fallback += float(self.params.global_mean)
            if self.params.item_bias is not None:
                fallback += np.asarray(
                    self.params.item_bias, np.float64
                ).reshape(-1)[items]
            pred = np.where(live, pred, fallback)
            w = batch_np.get("weight")
            w = (
                np.ones(users.shape, np.float64) if w is None
                else np.asarray(w, np.float64)
            )
            rating = np.asarray(batch_np["rating"], np.float64)
            total += float((np.abs(rating - pred) * w).sum())
            count += float(w.sum())
        return total / max(count, 1.0)
