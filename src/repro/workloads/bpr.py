"""BPR pairwise ranking (Rendle et al., UAI'09) under dynamic pruning.

BPR optimizes AUC-like pairwise order: for a user ``u``, an interacted item
``i`` and a sampled non-interacted item ``j``, minimize

    -log σ(s_ui - s_uj)  +  0.5·lam·(||x_u||² + ||y_i||² + ||y_j||²).

Every score ``s_ui = x_u·y_i`` is the latent dot product the paper's
dynamic pruning truncates: each pair stops at ``min(rank(x_u), rank(y_i))``
dims (the same ``effective_ranks`` / ``rank_mask`` machinery as
``mf.train_step``), regularization is masked by each row's own rank, and —
as in ``mf._train_step`` — the masks are treated as constants
(``stop_gradient``), so :func:`bpr_train_step` IS the exact gradient of the
masked loss.  Rate 0 recovers dense BPR bit-for-bit.  The differential
oracle tests pin both properties (``tests/test_workloads.py``): parity with
``jax.grad`` of the masked loss, and with the NumPy reference
``kernels.ref.bpr_step_ref`` on 1/8-grid factors.

The epoch driver mirrors the explicit path: :class:`BPRSampler` draws the
per-epoch (user, pos, neg) triples on the host (fresh negatives every
epoch, deterministic in ``(seed, epoch)``), and :func:`bpr_epoch_scan`
folds :func:`bpr_train_step` over the uploaded triples with the same
donated ``lax.scan`` as ``mf.train_epoch_scan``.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mf
from repro.core.ranks import effective_ranks, rank_mask
from repro.data.ratings import RatingsDataset
from repro.optim.optimizers import RowOptimizer, rows_written
from repro.workloads.implicit import _positive_sets, _sample_negatives


def _bpr_train_step(
    params: mf.MFParams,
    opt_state: mf.MFOptState,
    batch: Dict[str, jax.Array],   # {"user", "pos", "neg", opt. "weight"}
    t_p: jax.Array,
    t_q: jax.Array,
    lr: jax.Array,
    dim_mask: jax.Array,
    *,
    opt: RowOptimizer,
    lam: float,
) -> Tuple[mf.MFParams, mf.MFOptState, Dict[str, jax.Array]]:
    """One pruned BPR update on (user, pos, neg) triples.

    Pair scores truncate at ``min(r_u, r_item)`` exactly like
    ``predict_pairs``; the regularizer is masked by each row's own rank.
    With ``params.user_bias`` present the item bias joins the score (the
    user bias and global mean cancel in the pairwise difference and stay
    untouched).  An optional ``batch["weight"]`` gates triples out of the
    update and the metrics, mirroring ``train_step``'s weight contract
    (weight 0 = triple fully inert under SGD/Adagrad).  Both positive and
    negative q-rows scatter through ONE ``apply_rows`` call on concatenated
    indices, so a triple whose ``pos == neg`` accumulates additively
    (duplicate-safe) instead of racing.
    """
    u, i, j = batch["user"], batch["pos"], batch["neg"]
    weight = batch.get("weight")
    k = params.p.shape[-1]

    x_u = params.p[u]
    y_i = params.q[i]
    y_j = params.q[j]
    r_u = effective_ranks(x_u, t_p)
    r_i = effective_ranks(y_i, t_q)
    r_j = effective_ranks(y_j, t_q)
    rank_ui = jnp.minimum(r_u, r_i)
    rank_uj = jnp.minimum(r_u, r_j)
    m_ui = rank_mask(rank_ui, k) * dim_mask[None, :]
    m_uj = rank_mask(rank_uj, k) * dim_mask[None, :]
    m_u = rank_mask(r_u, k) * dim_mask[None, :]
    m_i = rank_mask(r_i, k) * dim_mask[None, :]
    m_j = rank_mask(r_j, k) * dim_mask[None, :]

    xf = x_u.astype(jnp.float32)
    yif = y_i.astype(jnp.float32)
    yjf = y_j.astype(jnp.float32)
    s_ui = jnp.sum(xf * yif * m_ui, axis=-1)
    s_uj = jnp.sum(xf * yjf * m_uj, axis=-1)
    if params.item_bias is not None:
        s_ui = s_ui + params.item_bias[i, 0]
        s_uj = s_uj + params.item_bias[j, 0]
    diff = s_ui - s_uj
    # d(-log σ(diff))/d(diff) = -(1 - σ(diff)) = -σ(-diff)
    sig = jax.nn.sigmoid(-diff)
    w = (
        jnp.ones_like(diff) if weight is None else weight.astype(jnp.float32)
    )

    g_p = -sig[:, None] * (yif * m_ui - yjf * m_uj) + lam * xf * m_u
    g_qi = -sig[:, None] * xf * m_ui + lam * yif * m_i
    g_qj = sig[:, None] * xf * m_uj + lam * yjf * m_j

    w_col = jnp.broadcast_to(w[:, None], (w.shape[0], k))
    new_p, st_p = opt.apply_rows(params.p, opt_state.p, u, g_p, w_col, lr)
    idx_q = jnp.concatenate([i, j])
    g_q = jnp.concatenate([g_qi, g_qj])
    mask_q = jnp.concatenate([w_col, w_col])
    new_q, st_q = opt.apply_rows(params.q, opt_state.q, idx_q, g_q, mask_q, lr)
    new_params = params._replace(p=new_p, q=new_q)
    new_state = opt_state._replace(p=st_p, q=st_q)

    if params.item_bias is not None:
        g_bi = -sig[:, None] + lam * params.item_bias[i]
        g_bj = sig[:, None] + lam * params.item_bias[j]
        new_bi, st_bi = opt.apply_rows(
            params.item_bias, opt_state.item_bias, idx_q,
            jnp.concatenate([g_bi, g_bj]),
            jnp.concatenate([w[:, None], w[:, None]]), lr,
        )
        new_params = new_params._replace(item_bias=new_bi)
        new_state = new_state._replace(item_bias=st_bi)

    denom = jnp.maximum(jnp.sum(w), 1e-9)
    loss = jnp.log1p(jnp.exp(-jnp.abs(diff))) + jnp.maximum(-diff, 0.0)
    metrics = {
        # abs_err carries the mean BPR loss so the shared epoch-scan
        # accumulators (and EpochRecord.train_abs_err) stay meaningful
        "abs_err": jnp.sum(loss * w) / denom,
        "work_fraction": jnp.sum(
            (rank_ui + rank_uj).astype(jnp.float32) * w
        ) / (denom * 2 * k),
        # the sorts of the two apply_rows calls above, shared
        "user_rows_share": rows_written(
            u, jnp.any(w_col != 0, axis=-1), params.p.shape[0]
        ) / u.shape[0],
        "item_rows_share": rows_written(
            idx_q, jnp.any(mask_q != 0, axis=-1), params.q.shape[0]
        ) / idx_q.shape[0],
    }
    return new_params, new_state, metrics


bpr_train_step = jax.jit(_bpr_train_step, static_argnames=("opt", "lam"))


@functools.partial(
    jax.jit, static_argnames=("opt", "lam"), donate_argnums=(0, 1)
)
def bpr_epoch_scan(
    params: mf.MFParams,
    opt_state: mf.MFOptState,
    batches: Dict[str, jax.Array],   # each value (steps, B)
    t_p: jax.Array,
    t_q: jax.Array,
    lr: jax.Array,
    dim_mask: jax.Array,
    *,
    opt: RowOptimizer,
    lam: float,
) -> Tuple[mf.MFParams, mf.MFOptState, Dict[str, jax.Array]]:
    """A whole BPR epoch as one donated computation — the pairwise analogue
    of ``mf.train_epoch_scan``, folding :func:`bpr_train_step` over packed
    (user, pos, neg) triples with the shared ``mf._epoch_scan`` body."""

    def step(p, s, batch):
        return _bpr_train_step(
            p, s, batch, t_p, t_q, lr, dim_mask, opt=opt, lam=lam
        )

    return mf._epoch_scan(step, params, opt_state, batches)


class BPRSampler:
    """Per-epoch (user, pos, neg) triples from an interaction log.

    Every interaction of ``ds`` is a positive; negatives are drawn fresh
    each epoch, uniformly over the catalog with rejection against the
    user's positive set (:func:`~repro.workloads.implicit._sample_negatives`
    semantics).  Deterministic in ``(seed, epoch)`` like the training
    loader, so checkpoint restarts replay identical triples.  Triples are
    uploaded per epoch as ``(steps, B)`` device arrays — the operand of
    :func:`bpr_epoch_scan`.
    """

    def __init__(self, ds: RatingsDataset, batch_size: int, *, seed: int = 0):
        self.user = np.asarray(ds.user, np.int32)
        self.item = np.asarray(ds.item, np.int32)
        self.num_items = ds.num_items
        self.seed = seed
        self.batch_size = min(int(batch_size), max(self.user.size, 1))
        self._pos_sets = _positive_sets(self.user, self.item, ds.num_users)

    @property
    def num_steps(self) -> int:
        return self.user.size // self.batch_size

    def epoch_triples(self, epoch: int) -> Dict[str, jnp.ndarray]:
        """Shuffled positives + fresh negatives for one epoch, shaped
        ``(steps, batch_size)`` on device."""
        if self.num_steps == 0:
            raise ValueError(
                f"batch_size {self.batch_size} exceeds the dataset "
                f"({self.user.size} interactions)"
            )
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, epoch, 0xB9])
        )
        take = rng.permutation(self.user.size)[
            : self.num_steps * self.batch_size
        ]
        users = self.user[take]
        pos = self.item[take]
        neg = _sample_negatives(rng, users, self._pos_sets, self.num_items)
        shape = (self.num_steps, self.batch_size)
        return {
            "user": jnp.asarray(users.reshape(shape)),
            "pos": jnp.asarray(pos.reshape(shape)),
            "neg": jnp.asarray(neg.reshape(shape)),
        }
