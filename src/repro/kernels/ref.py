"""Pure-jnp oracles for the Pallas kernels, bit-exact to the paper's loops.

Every kernel in this package is validated against these references across
shape/dtype sweeps (``tests/test_kernels.py``).  ``early_stop_dot_loop`` is
additionally a direct numpy transcription of the paper's Algorithm 2 used by
the hypothesis property tests to pin the masked formulation to the paper.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.ranks import effective_ranks, rank_mask


def masked_factors(rows: jax.Array, ranks: jax.Array) -> jax.Array:
    """Zero columns ``t >= rank`` of each row."""
    return rows * rank_mask(ranks, rows.shape[-1], rows.dtype)


def pruned_matmul_ref(
    p: jax.Array,  # (m, k)
    q: jax.Array,  # (n, k)  item-major
    r_u: jax.Array,  # (m,) int32
    r_i: jax.Array,  # (n,) int32
    *,
    out_dtype=jnp.float32,
) -> jax.Array:
    """All-pairs early-stopped product: out[u, i] = sum_{t < min(r_u, r_i)}.

    Masking each operand by its own rank makes the product mask the AND of
    the two prefix masks, i.e. exactly ``t < min(r_u, r_i)``.  Precision is
    pinned to HIGHEST: the oracle must be float32-accurate, and a TPU's
    default float32 matmul precision is lower (no-op on the CPU).
    """
    pm = masked_factors(p, r_u).astype(jnp.float32)
    qm = masked_factors(q, r_i).astype(jnp.float32)
    return jnp.dot(
        pm, qm.T, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(out_dtype)


def pruned_topk_ref(
    p: jax.Array,    # (m, k)
    q: jax.Array,    # (n, k)
    r_u: jax.Array,  # (m,) int32
    r_i: jax.Array,  # (n,) int32
    topk: int,
    *,
    item_bias: jax.Array | None = None,  # (n,) folded in before ranking
):
    """Serving oracle: dense pruned scores, full argsort, take top-k.

    Deliberately materializes the (m, n) score matrix — this is the
    score-everything-then-argsort baseline the serving engine replaces; the
    engine's streaming paths must return identical (scores, indices).
    Stable argsort resolves score ties toward the lower item index, matching
    the streaming merges (earlier tiles win ties).
    """
    scores = pruned_matmul_ref(p, q, r_u, r_i)
    if item_bias is not None:
        scores = scores + item_bias[None, :].astype(jnp.float32)
    order = jnp.argsort(-scores, axis=1)[:, :topk].astype(jnp.int32)
    return jnp.take_along_axis(scores, order, axis=1), order


def pruned_pair_dot_ref(
    p_rows: jax.Array,  # (b, k)
    q_rows: jax.Array,  # (b, k)
    r_u: jax.Array,     # (b,)
    r_i: jax.Array,     # (b,)
) -> jax.Array:
    pm = masked_factors(p_rows, r_u).astype(jnp.float32)
    qm = masked_factors(q_rows, r_i).astype(jnp.float32)
    return jnp.sum(pm * qm, axis=-1)


def fused_mf_sgd_ref(
    p_rows: jax.Array,   # (b, k) gathered user factors
    q_rows: jax.Array,   # (b, k) gathered item factors
    ratings: jax.Array,  # (b,)
    t_p: jax.Array,
    t_q: jax.Array,
    *,
    lr: float,
    lam: float,
    bias_u: jax.Array | None = None,   # (b,) gathered user biases
    bias_i: jax.Array | None = None,   # (b,) gathered item biases
    global_mean: jax.Array | float = 0.0,
    weight: jax.Array | None = None,   # (b,) update gate / importance weight
):
    """Alg. 2 + Alg. 3 fused: masked dot, error, masked SGD row updates.

    Returns ``(new_p_rows, new_q_rows, new_bias_u, new_bias_i, err)`` —
    the bias outputs are None when the inputs are.  Ranks are computed from
    the *current* row values (dynamic pruning); the update touches only the
    computed prefix ``t < min(r_u, r_i)``, per Eq. 5/6 restricted by Alg. 3.
    ``weight`` scales the updates only (0 = inert row); the prediction —
    including biases and the global mean — is always the full model output,
    so the error matches ``mf.train_step``.
    """
    k = p_rows.shape[-1]
    r_u = effective_ranks(p_rows, t_p)
    r_i = effective_ranks(q_rows, t_q)
    mask = rank_mask(jnp.minimum(r_u, r_i), k, jnp.float32)
    w = (
        jnp.ones((p_rows.shape[0],), jnp.float32)
        if weight is None
        else weight.astype(jnp.float32)
    )

    pf = p_rows.astype(jnp.float32)
    qf = q_rows.astype(jnp.float32)
    pred = jnp.sum(pf * qf * mask, axis=-1)
    if bias_u is not None:
        pred = (
            pred
            + jnp.asarray(global_mean, jnp.float32)
            + bias_u.astype(jnp.float32)
            + bias_i.astype(jnp.float32)
        )
    err = ratings.astype(jnp.float32) - pred

    wm = mask * w[:, None]
    new_p = pf + lr * (err[:, None] * qf - lam * pf) * wm
    new_q = qf + lr * (err[:, None] * pf - lam * qf) * wm
    new_bu = new_bi = None
    if bias_u is not None:
        buf = bias_u.astype(jnp.float32)
        bif = bias_i.astype(jnp.float32)
        new_bu = (buf + lr * (err - lam * buf) * w).astype(bias_u.dtype)
        new_bi = (bif + lr * (err - lam * bif) * w).astype(bias_i.dtype)
    return new_p.astype(p_rows.dtype), new_q.astype(q_rows.dtype), new_bu, new_bi, err


def _ranks_np(rows: np.ndarray, threshold: float) -> np.ndarray:
    """NumPy transcription of :func:`repro.core.ranks.effective_ranks`."""
    insig = np.abs(rows) < threshold
    first = np.argmax(insig, axis=-1).astype(np.int32)
    return np.where(np.any(insig, axis=-1), first, rows.shape[-1]).astype(
        np.int32
    )


def _rank_mask_np(ranks: np.ndarray, k: int) -> np.ndarray:
    return (np.arange(k)[None, :] < ranks[:, None]).astype(np.float32)


def bpr_step_ref(
    p: np.ndarray,         # (m, k) full user table
    q: np.ndarray,         # (n, k) full item table
    user: np.ndarray,      # (b,)
    pos: np.ndarray,       # (b,)
    neg: np.ndarray,       # (b,)
    t_p: float,
    t_q: float,
    *,
    lr: float,
    lam: float,
    item_bias: np.ndarray | None = None,   # (n,) optional
    weight: np.ndarray | None = None,      # (b,) update gate
):
    """NumPy reference for one plain-SGD pruned BPR step (whole tables).

    The differential oracle for ``workloads.bpr.bpr_train_step``: pair
    scores truncate at ``min(r_u, r_item)``, regularization masks by each
    row's own rank, duplicate rows accumulate additively (``np.add.at``,
    matching the scatter-add), all in float32 so grid-valued factors match
    the jitted step exactly.  Returns ``(new_p, new_q, new_item_bias,
    mean_loss)``.
    """
    k = p.shape[-1]
    pf = p.astype(np.float32)
    qf = q.astype(np.float32)
    x_u, y_i, y_j = pf[user], qf[pos], qf[neg]
    r_u = _ranks_np(x_u, t_p)
    r_i = _ranks_np(y_i, t_q)
    r_j = _ranks_np(y_j, t_q)
    m_ui = _rank_mask_np(np.minimum(r_u, r_i), k)
    m_uj = _rank_mask_np(np.minimum(r_u, r_j), k)
    m_u = _rank_mask_np(r_u, k)
    m_i = _rank_mask_np(r_i, k)
    m_j = _rank_mask_np(r_j, k)

    s_ui = np.sum(x_u * y_i * m_ui, axis=-1, dtype=np.float32)
    s_uj = np.sum(x_u * y_j * m_uj, axis=-1, dtype=np.float32)
    new_bias = None
    if item_bias is not None:
        bf = item_bias.astype(np.float32)
        s_ui = s_ui + bf[pos]
        s_uj = s_uj + bf[neg]
    diff = (s_ui - s_uj).astype(np.float32)
    sig = (1.0 / (1.0 + np.exp(diff))).astype(np.float32)  # σ(-diff)
    w = (
        np.ones_like(diff) if weight is None
        else weight.astype(np.float32)
    )

    g_p = (-sig[:, None] * (y_i * m_ui - y_j * m_uj) + lam * x_u * m_u)
    g_qi = (-sig[:, None] * x_u * m_ui + lam * y_i * m_i)
    g_qj = (sig[:, None] * x_u * m_uj + lam * y_j * m_j)
    new_p = pf.copy()
    new_q = qf.copy()
    np.add.at(new_p, user, (-lr * g_p * w[:, None]).astype(np.float32))
    np.add.at(new_q, pos, (-lr * g_qi * w[:, None]).astype(np.float32))
    np.add.at(new_q, neg, (-lr * g_qj * w[:, None]).astype(np.float32))
    if item_bias is not None:
        new_bias = item_bias.astype(np.float32).copy()
        np.add.at(new_bias, pos, -lr * (-sig + lam * bf[pos]) * w)
        np.add.at(new_bias, neg, -lr * (sig + lam * bf[neg]) * w)
    loss = np.log1p(np.exp(-np.abs(diff))) + np.maximum(-diff, 0.0)
    denom = max(float(w.sum()), 1e-9)
    return new_p, new_q, new_bias, float((loss * w).sum() / denom)


def early_stop_dot_loop(
    p_row: np.ndarray, q_row: np.ndarray, t_p: float, t_q: float
) -> float:
    """Direct transcription of the paper's Algorithm 2 (scalar, CPU)."""
    acc = 0.0
    for t in range(p_row.shape[0]):
        if abs(float(p_row[t])) < t_p or abs(float(q_row[t])) < t_q:
            break
        acc += float(p_row[t]) * float(q_row[t])
    return acc


def early_stop_update_loop(
    p_row: np.ndarray,
    q_row: np.ndarray,
    rating: float,
    t_p: float,
    t_q: float,
    lr: float,
    lam: float,
):
    """Algorithm 3 (scalar): prediction with Alg. 2 then truncated Eq. 5/6."""
    pred = early_stop_dot_loop(p_row, q_row, t_p, t_q)
    err = rating - pred
    new_p = p_row.astype(np.float64).copy()
    new_q = q_row.astype(np.float64).copy()
    for t in range(p_row.shape[0]):
        if abs(float(p_row[t])) < t_p or abs(float(q_row[t])) < t_q:
            break
        new_p[t] = p_row[t] + lr * (err * q_row[t] - lam * p_row[t])
        new_q[t] = q_row[t] + lr * (err * p_row[t] - lam * q_row[t])
    return new_p, new_q, err
