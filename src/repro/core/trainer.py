"""DP-MF training driver — the paper's overall procedure (Figs. 6 & 10).

Schedule:
  epoch 1   : standard (unpruned) training — thresholds don't exist yet
  after ep 1: measure (mu, sigma) of P and Q  -> T_p, T_q   (§4.2, once)
              rearrange latent axis by joint sparsity        (§4.3, once)
  epoch 2.. : dynamically pruned training                    (§4.4, per batch)

The dense baseline is the same driver with ``pruning_rate = 0`` (thresholds
collapse to 0 and every mask is all-ones — one code path, as in the paper's
"runtime of the conventional training process is measured by setting the
pruning rate as 0").
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_lib
from repro import tracing
from repro.core import mf, rearrange, threshold
from repro.data import loader
from repro.data.ratings import RatingsDataset, build_user_history
from repro.distributed.fault_tolerance import (
    StragglerDetector,
    run_with_retries,
)
from repro.optim.optimizers import RowOptimizer
from repro.optim.schedules import twin_learners_mask
from repro.testing import faults


@dataclasses.dataclass
class TrainConfig:
    k: int = 50
    epochs: int = 15
    batch_size: int = 4096
    lr: float = 0.05
    lam: float = 0.02
    pruning_rate: float = 0.0          # 0 disables pruning (dense baseline)
    optimizer: str = "adagrad"         # LibMF's default, as in the paper
    strategy: str = "standard"         # standard | twin  (paper §5.3)
    init_method: str = "normal"        # normal | uniform (paper §5.3)
    variant: str = "funk"              # funk | bias | svdpp
    # -- training objective (repro.workloads) -------------------------------
    # explicit: squared rating error (the paper's setting)
    # implicit: WALS confidence-weighted binary preference (Hu et al. 2008)
    #           — the interaction log is expanded once at init into
    #           positives + sampled negatives with a confidence weight
    #           column riding train_step's batch["weight"] gate
    # bpr:      pairwise -log σ(s_ui - s_uj) on per-epoch sampled triples
    #           (test_mae is NaN, ranking metrics carry)
    objective: str = "explicit"        # explicit | implicit | bpr
    implicit_alpha: float = 40.0       # confidence c = 1 + alpha·r
    implicit_negatives: int = 4        # sampled unobserved items / positive
    seed: int = 0
    eval_batch_size: int = 8192
    max_hist: int = 32                 # svd++ implicit history length
    rearrange: bool = True             # Alg. 1; False = ablation (§Repro)
    ranking_topk: int = 0              # >0: per-epoch HR/NDCG/recall@K too
    ranking_max_users: Optional[int] = 512   # eval-user cap for ranking
    checkpoint_dir: Optional[str] = None
    checkpoint_every_epochs: int = 0   # 0 = only final
    keep_checkpoints: int = 3
    # -- out-of-core streaming (src/repro/store) ----------------------------
    store_dir: Optional[str] = None    # train from an on-disk RatingsStore
    slab_steps: int = 256              # steps per streamed slab
    prefetch_slabs: int = 2            # bounded host prefetch queue depth
    checkpoint_every_slabs: int = 0    # 0 = no mid-epoch checkpoints
    # bounded retries around each streamed slab (store mode): a transient
    # step failure re-runs the slab instead of killing the epoch.  Safe
    # because failures injected/raised before dispatch leave params
    # untouched; 0 disables the wrapper entirely.
    max_step_retries: int = 0
    # -- distributed gradient exchange (shard_map path) ---------------------
    grad_compression: str = "none"     # none | int8 | int8_ef


@dataclasses.dataclass
class EpochRecord:
    """One epoch's logged measurements (``DPMFTrainer.history`` entries).

    The ranking fields are NaN unless ``TrainConfig.ranking_topk > 0`` —
    they come from ``mf.eval_ranking_epoch_scan`` over the test split, so
    the accuracy trajectory carries the served quantity (top-k quality),
    not only the paper's rating error.
    """

    epoch: int
    wall_time_s: float
    train_abs_err: float
    test_mae: float
    work_fraction: float   # mean k_eff / k — the work-proportional cost
    t_p: float
    t_q: float
    hr: float = float("nan")       # HR@K at ranking_topk
    ndcg: float = float("nan")     # NDCG@K
    recall: float = float("nan")   # recall@K
    straggler_slabs: int = 0       # slabs flagged as wall-time outliers
    step_retries: int = 0          # slab retries consumed this epoch
    # mean share of a step's ids that are distinct rows of P / Q written
    # (NaN on the store path, which logs only the error and the work), and
    # SVD++'s distinct rows of Y written over its live history occurrences
    user_rows_share: float = float("nan")
    item_rows_share: float = float("nan")
    implicit_rows_share: float = float("nan")


class DPMFTrainer:
    """End-to-end trainer implementing the paper + checkpoint/restart."""

    def __init__(
        self,
        config: TrainConfig,
        train_ds: Optional[RatingsDataset] = None,
        test_ds: Optional[RatingsDataset] = None,
    ):
        with tracing.span("repro.trainer.init"):
            self.config = config
            self.opt = RowOptimizer(name=config.optimizer)
            if config.objective not in ("explicit", "implicit", "bpr"):
                raise ValueError(f"unknown objective {config.objective!r}")
            self._train_weight = None      # implicit confidence column
            self._bpr_sampler = None
            if config.objective != "explicit":
                if config.store_dir is not None:
                    raise ValueError(
                        "store-backed training supports only the explicit "
                        "objective"
                    )
                if config.variant == "svdpp":
                    raise ValueError(
                        "svdpp histories assume a rated log; use variant "
                        "'funk' or 'bias' with implicit/bpr objectives"
                    )
                if train_ds is None:
                    raise ValueError(
                        f"objective {config.objective!r} requires train_ds"
                    )
            if config.objective == "implicit":
                from repro.workloads import implicit as implicit_wl

                # one-time expansion: positives + sampled negatives, with the
                # WALS confidence column carried as per-example weights
                train_ds, self._train_weight = implicit_wl.implicit_dataset(
                    train_ds,
                    alpha=config.implicit_alpha,
                    negatives=config.implicit_negatives,
                    seed=config.seed,
                )
                if test_ds is not None:
                    # held-out interactions as preference-1 targets: test MAE
                    # reads "distance from 1 on the user's actual items"
                    test_ds = implicit_wl.binarize_positives(test_ds)
            self.train_ds = train_ds
            self.test_ds = test_ds
            self._store = None
            self._loader = None
            self._resume_slab = 0
            self._resume_sums = (0.0, 0.0, 0)   # (err_sum, work_sum, steps_done)
            # slab-level fault tolerance: wall-time outlier detection feeding
            # the epoch record, plus an optional test-injected failure source
            # (FailureInjector) exercised under TrainConfig.max_step_retries
            self.straggler = StragglerDetector(window=20, z_threshold=4.0)
            self.failure_injector = None
            self._slab_counter = 0              # global slab index across epochs
            if config.store_dir is not None:
                # Out-of-core path: the ratings stay on disk (mmap) and stream
                # through a bounded prefetch queue as (slab_steps, B) slabs —
                # host memory is bounded by the queue depth, not the dataset.
                from repro.store import RatingsStore, ShardedRatingsLoader

                if config.variant == "svdpp":
                    raise ValueError(
                        "store-backed training does not support svdpp (the "
                        "implicit-history matrix is itself O(users))"
                    )
                self._store = RatingsStore(config.store_dir)
                self._loader = ShardedRatingsLoader(
                    self._store,
                    config.batch_size,
                    slab_steps=config.slab_steps,
                    prefetch=config.prefetch_slabs,
                )
            elif train_ds is None:
                raise ValueError("either train_ds or config.store_dir is required")
            self.hist = None
            if config.variant == "svdpp":
                with tracing.span("repro.trainer.history") as sp:
                    self.hist, truncated = build_user_history(train_ds, config.max_hist)
                    sp.set_metadata(
                        users=train_ds.num_users,
                        slots=int(np.sum(self.hist < train_ds.num_items)),
                        truncated=truncated,
                    )
            # Upload the ratings (and eval set / SVD++ history) ONCE;
            # per-epoch reshuffles happen on device (data/loader.py).  The
            # batch size is clamped so a tiny dataset trains as one batch
            # per epoch instead of degenerating to zero steps (which is
            # what a drop-remainder batch loop silently does).  In store
            # mode the train table never lands on device wholesale.
            self._packed_train = (
                loader.pack_ratings(
                    train_ds,
                    min(config.batch_size, max(len(train_ds), 1)),
                    weight=self._train_weight,
                )
                if self._loader is None and config.objective != "bpr"
                else None
            )
            if config.objective == "bpr":
                from repro.workloads.bpr import BPRSampler

                self._bpr_sampler = BPRSampler(
                    train_ds, config.batch_size, seed=config.seed
                )
            self._packed_eval = (
                loader.pack_eval_batches(test_ds, config.eval_batch_size)
                if test_ds is not None
                else None
            )
            self._hist_dev = None if self.hist is None else jnp.asarray(self.hist)
            self._packed_ranking = None
            if config.ranking_topk > 0 and test_ds is not None:
                from repro.eval import ranking as ranking_eval

                self._packed_ranking = ranking_eval.pack_ranking_batches(
                    test_ds, batch_size=256, max_users=config.ranking_max_users
                )

            rng = jax.random.PRNGKey(config.seed)
            src = train_ds if train_ds is not None else self._store
            self.params = mf.init_params(
                rng,
                src.num_users,
                src.num_items,
                config.k,
                variant=config.variant,
                init_method=config.init_method,
                global_mean=src.global_mean,
            )
            self.opt_state = mf.init_opt_state(self.params, self.opt)
            self.t_p = jnp.float32(0.0)
            self.t_q = jnp.float32(0.0)
            self.perm: Optional[jax.Array] = None
            self.epoch = 0
            self.history: List[EpochRecord] = []
            self._ckpt = (
                ckpt_lib.AsyncCheckpointer(
                    config.checkpoint_dir, keep=config.keep_checkpoints
                )
                if config.checkpoint_dir
                else None
            )

    # -- checkpoint/restart ------------------------------------------------
    def _state_tree(self) -> Dict[str, Any]:
        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "t_p": self.t_p,
            "t_q": self.t_q,
            "perm": self.perm if self.perm is not None else jnp.arange(
                self.config.k, dtype=jnp.int32
            ),
        }

    def _ckpt_step(self, slabs_done: int = 0) -> int:
        """Checkpoint step numbering.

        Epoch-granular runs use the epoch count directly.  Store-backed runs
        number by slab — ``epoch * num_slabs + slabs_done`` — so an
        epoch-boundary save and a mid-epoch save can never collide, and
        steps stay monotonic across the whole run.
        """
        if self._loader is None:
            return self.epoch
        return self.epoch * self._loader.num_slabs + slabs_done

    def save(self, step: int, *, extra_metadata: Optional[Dict[str, Any]] = None) -> None:
        if self._ckpt is None:
            return
        metadata = {
            "epoch": self.epoch,
            "seed": self.config.seed,
            "pruning_rate": self.config.pruning_rate,
        }
        if extra_metadata:
            metadata.update(extra_metadata)
        self._ckpt.save(step, self._state_tree(), metadata=metadata)

    def _save_mid_epoch(
        self, slabs_done: int, err_sum: float, work_sum: float, steps_done: int
    ) -> None:
        """Checkpoint inside an epoch (store mode): params/opt_state plus the
        running metric accumulators, so a restart replays only the remaining
        slabs and still reports the identical epoch metrics."""
        self.save(
            self._ckpt_step(slabs_done),
            extra_metadata={
                "slab_idx": slabs_done,
                "err_sum": err_sum,
                "work_sum": work_sum,
                "steps_done": steps_done,
            },
        )

    def maybe_restore(self) -> bool:
        if self.config.checkpoint_dir is None:
            return False
        if ckpt_lib.latest_step(self.config.checkpoint_dir) is None:
            return False
        tree, meta = ckpt_lib.restore(self.config.checkpoint_dir, self._state_tree())
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        self.t_p = jnp.asarray(tree["t_p"], jnp.float32)
        self.t_q = jnp.asarray(tree["t_q"], jnp.float32)
        self.perm = tree["perm"]
        self.epoch = int(meta["epoch"])
        self._resume_slab = int(meta.get("slab_idx", 0))
        self._resume_sums = (
            float(meta.get("err_sum", 0.0)),
            float(meta.get("work_sum", 0.0)),
            int(meta.get("steps_done", 0)),
        )
        return True

    # -- the paper's one-time calibration (after epoch 1) -------------------
    def calibrate(self) -> None:
        with tracing.span("repro.trainer.calibrate"):
            cfg = self.config
            if cfg.pruning_rate <= 0.0:
                return
            self.t_p, self.t_q = threshold.thresholds_from_matrices(
                self.params.p, self.params.q, cfg.pruning_rate
            )
            if not cfg.rearrange:  # ablation: prune without Algorithm 1
                self.perm = jnp.arange(cfg.k, dtype=jnp.int32)
                return
            result = rearrange.rearrangement(
                self.params.p, self.params.q, self.t_p, self.t_q
            )
            self.perm = result.perm
            new_p, new_q = rearrange.apply_perm(self.params.p, self.params.q, self.perm)
            self.params = self.params._replace(p=new_p, q=new_q)
            if self.params.implicit is not None:
                self.params = self.params._replace(
                    implicit=jnp.take(self.params.implicit, self.perm, axis=1)
                )
            # Keep optimizer accumulators aligned with the permuted latent axis.
            def permute_state(state):
                return {
                    key: (
                        jnp.take(value, self.perm, axis=1)
                        if getattr(value, "ndim", 0) == 2
                        and value.shape[1] == self.config.k
                        else value
                    )
                    for key, value in state.items()
                }

            self.opt_state = self.opt_state._replace(
                p=permute_state(self.opt_state.p),
                q=permute_state(self.opt_state.q),
                implicit=(
                    None
                    if self.opt_state.implicit is None
                    else permute_state(self.opt_state.implicit)
                ),
            )

    # -- epochs --------------------------------------------------------------
    def run_epoch(self) -> EpochRecord:
        cfg = self.config
        pruning_active = cfg.pruning_rate > 0.0 and self.epoch >= 1
        with tracing.span(
            "repro.trainer.epoch", epoch=self.epoch, pruned=int(pruning_active)
        ):
            t_p = self.t_p if pruning_active else jnp.float32(0.0)
            t_q = self.t_q if pruning_active else jnp.float32(0.0)
            dim_mask = (
                twin_learners_mask(cfg.k, self.epoch)
                if cfg.strategy == "twin"
                else jnp.ones((cfg.k,), jnp.float32)
            )
            lr = jnp.float32(cfg.lr)

            start = time.perf_counter()
            straggler_slabs = 0
            retry_count = [0]
            if self._loader is not None:
                # Store mode: the epoch is a sequence of slab-chunked scans fed
                # by the prefetch queue.  Metric means accumulate step-weighted
                # in host float64 so a mid-epoch resume (which restores the
                # partial sums from metadata) reports bitwise-identical epoch
                # numbers to an uninterrupted run — both execute this same
                # chunked path over the same deterministic slab order.
                err_sum, work_sum, steps_done = self._resume_sums
                start_slab = self._resume_slab
                self._resume_slab = 0
                self._resume_sums = (0.0, 0.0, 0)
                num_slabs = self._loader.num_slabs
                for slab in self._loader.epoch_slabs(
                    cfg.seed, self.epoch, start_slab=start_slab
                ):
                    def run_slab(slab=slab):
                        # faults fire BEFORE the dispatch so a retry re-runs
                        # the slab against untouched params (no donation hazard)
                        if self.failure_injector is not None:
                            self.failure_injector(self._slab_counter)
                        if faults._PLAN is not None:
                            for act in faults.fire("trainer.slab"):
                                if act.op == "error":
                                    raise faults.FaultError(
                                        "injected slab failure"
                                    )
                        return mf.train_epoch_scan(
                            self.params,
                            self.opt_state,
                            slab.batches,
                            t_p,
                            t_q,
                            lr,
                            dim_mask,
                            self._hist_dev,
                            opt=self.opt,
                            lam=cfg.lam,
                        )

                    slab_start = time.perf_counter()
                    if cfg.max_step_retries > 0:
                        self.params, self.opt_state, metrics = run_with_retries(
                            run_slab,
                            max_retries=cfg.max_step_retries,
                            backoff_s=0.05,
                            on_retry=lambda n, exc: retry_count.__setitem__(
                                0, retry_count[0] + 1
                            ),
                        )
                    else:
                        self.params, self.opt_state, metrics = run_slab()
                    jax.block_until_ready(self.params.p)
                    if self.straggler.record(time.perf_counter() - slab_start):
                        straggler_slabs += 1
                    self._slab_counter += 1
                    err_sum += float(metrics["abs_err"]) * slab.steps
                    work_sum += float(metrics["work_fraction"]) * slab.steps
                    steps_done += slab.steps
                    slabs_done = slab.slab_idx + 1
                    if (
                        self._ckpt is not None
                        and cfg.checkpoint_every_slabs
                        and slabs_done % cfg.checkpoint_every_slabs == 0
                        and slabs_done < num_slabs
                    ):
                        self._save_mid_epoch(slabs_done, err_sum, work_sum, steps_done)
                epoch_metrics = {
                    "abs_err": err_sum / max(steps_done, 1),
                    "work_fraction": work_sum / max(steps_done, 1),
                }
            elif cfg.objective == "bpr":
                # Pairwise epoch: freshly sampled (user, pos, neg) triples folded
                # through the same scan machinery; abs_err carries the BPR loss.
                from repro.workloads import bpr as bpr_wl

                triples = self._bpr_sampler.epoch_triples(self.epoch)
                self.params, self.opt_state, metrics = bpr_wl.bpr_epoch_scan(
                    self.params,
                    self.opt_state,
                    triples,
                    t_p,
                    t_q,
                    lr,
                    dim_mask,
                    opt=self.opt,
                    lam=cfg.lam,
                )
                jax.block_until_ready(self.params.p)
                epoch_metrics = jax.device_get(metrics)
            else:
                # One donated, compiled computation for the whole epoch: on-device
                # reshuffle, lax.scan of train_step, metrics summed on device.
                with tracing.span("repro.trainer.shuffle"):
                    batches = self._packed_train.epoch_batches(
                        cfg.seed, self.epoch
                    )
                with tracing.span("repro.trainer.step"):
                    self.params, self.opt_state, metrics = mf.train_epoch_scan(
                        self.params,
                        self.opt_state,
                        batches,
                        t_p,
                        t_q,
                        lr,
                        dim_mask,
                        self._hist_dev,
                        opt=self.opt,
                        lam=cfg.lam,
                    )
                with tracing.span("repro.trainer.sync"):
                    jax.block_until_ready(self.params.p)
                    # the epoch's single host sync: a few scalars
                    epoch_metrics = jax.device_get(metrics)
            abs_err = float(epoch_metrics["abs_err"])
            work = float(epoch_metrics["work_fraction"])
            rows_shares = {
                key: float(epoch_metrics[key])
                for key in ("user_rows_share", "item_rows_share", "implicit_rows_share")
                if key in epoch_metrics
            }
            wall = time.perf_counter() - start

            with tracing.span("repro.trainer.evaluate"):
                test_mae = (
                    self.evaluate(t_p, t_q)
                    if self.test_ds is not None else float("nan")
                )
                ranking = self.evaluate_ranking(t_p, t_q)
            record = EpochRecord(
                epoch=self.epoch,
                wall_time_s=wall,
                train_abs_err=abs_err,
                test_mae=test_mae,
                work_fraction=work,
                t_p=float(t_p),
                t_q=float(t_q),
                straggler_slabs=straggler_slabs,
                step_retries=retry_count[0],
                **rows_shares,
                **(
                    {"hr": ranking.hr, "ndcg": ranking.ndcg,
                     "recall": ranking.recall}
                    if ranking is not None else {}
                ),
            )
            self.history.append(record)

            if self.epoch == 0:
                self.calibrate()  # paper: once, right after the first epoch
            self.epoch += 1
            if (
                self._ckpt is not None
                and cfg.checkpoint_every_epochs
                and self.epoch % cfg.checkpoint_every_epochs == 0
            ):
                self.save(self._ckpt_step())
            return record

    def run(self) -> List[EpochRecord]:
        start_epoch = self.epoch
        for _ in range(start_epoch, self.config.epochs):
            self.run_epoch()
        if self._ckpt is not None:
            self.save(self._ckpt_step())
            self._ckpt.wait()
        return self.history

    def evaluate(self, t_p=None, t_q=None) -> float:
        """Test MAE (Eq. 12) with the current pruning thresholds.

        NaN when there is no test split, and under the ``bpr`` objective —
        pairwise scores have no rating scale, so rating error is undefined;
        use :meth:`evaluate_ranking` there instead.
        """
        if self.test_ds is None or self.config.objective == "bpr":
            return float("nan")
        t_p = self.t_p if t_p is None else t_p
        t_q = self.t_q if t_q is None else t_q
        total, count = mf.eval_epoch_scan(
            self.params, self._packed_eval, t_p, t_q, self._hist_dev
        )
        return float(total) / max(float(count), 1.0)

    def evaluate_ranking(self, t_p=None, t_q=None):
        """Test-split HR/NDCG/recall@``ranking_topk`` at the given (default:
        current) thresholds, as a :class:`~repro.eval.ranking.RankingReport`.
        Returns None unless ``TrainConfig.ranking_topk > 0`` and a test
        split exists.  Runs as one compiled scan
        (``mf.eval_ranking_epoch_scan``) over batches packed at init."""
        if self._packed_ranking is None:
            return None
        from repro.eval import ranking as ranking_eval

        t_p = self.t_p if t_p is None else t_p
        t_q = self.t_q if t_q is None else t_q
        sums = mf.eval_ranking_epoch_scan(
            self.params, self._packed_ranking, t_p, t_q, self._hist_dev,
            topk=self.config.ranking_topk,
        )
        return ranking_eval.report_from_sums(
            {key: float(value) for key, value in sums.items()},
            self.config.ranking_topk,
        )

    # -- summary metrics matching the paper's Eqs. 12-14 ---------------------
    def total_train_time(self) -> float:
        return sum(r.wall_time_s for r in self.history)

    def mean_work_fraction(self) -> float:
        pruned = [r.work_fraction for r in self.history if r.epoch >= 1]
        return float(np.mean(pruned)) if pruned else 1.0


def percentage_mae(mae_accelerated: float, mae_original: float) -> float:
    """Eq. 13."""
    return (mae_accelerated - mae_original) / mae_original * 100.0


def work_speedup(history: List[EpochRecord]) -> float:
    """Work-proportional speedup: dense MACs / executed MACs over the whole
    run (epoch 1 is always dense, as in the paper)."""
    total = len(history)
    if total == 0:
        return 1.0
    executed = sum(r.work_fraction for r in history)
    return total / max(executed, 1e-9)
