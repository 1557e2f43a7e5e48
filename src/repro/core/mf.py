"""MF model family: FunkSVD, BiasSVD, SVD++ with first-class dynamic pruning.

The paper develops its method on FunkSVD and notes it applies unchanged to
BiasSVD and SVD++ ("they have the same training process"); all three are
implemented here behind one step function.  Pruning is always expressed
through thresholds ``(t_p, t_q)`` — passing zeros disables it *numerically*
(no factor satisfies ``|v| < 0``), so the dense baseline and the accelerated
path share one code path and one compiled program.

Conventions: ``p`` is (m, k) user-major, ``q`` is (n, k) item-major (the
paper's ``Q_{k x n}`` transposed), biases are (rows, 1) so the row-optimizer
API applies uniformly.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ranks import effective_ranks, rank_mask
from repro.kernels import ops as kops
from repro.optim.optimizers import RowOptimizer, add_rows, rows_written

Batch = Dict[str, jax.Array]


class MFParams(NamedTuple):
    p: jax.Array                       # (m, k)
    q: jax.Array                       # (n, k)
    user_bias: Optional[jax.Array]     # (m, 1) | None
    item_bias: Optional[jax.Array]     # (n, 1) | None
    global_mean: Optional[jax.Array]   # ()     | None
    implicit: Optional[jax.Array]      # (n + 1, k) | None; row n is padding


def init_params(
    rng: jax.Array,
    num_users: int,
    num_items: int,
    k: int,
    *,
    variant: str = "funk",          # funk | bias | svdpp
    init_method: str = "normal",    # normal | uniform | libmf  (paper §5.3)
    scale: float = 0.1,
    global_mean: float = 0.0,
    dtype=jnp.float32,
) -> MFParams:
    kp, kq, ky = jax.random.split(rng, 3)
    if init_method == "normal":
        p = scale * jax.random.normal(kp, (num_users, k), dtype)
        q = scale * jax.random.normal(kq, (num_items, k), dtype)
        y = scale * jax.random.normal(ky, (num_items + 1, k), dtype)
    elif init_method == "uniform":
        # Same std as the normal init so thresholds are comparable.
        lim = scale * (3.0 ** 0.5)
        p = jax.random.uniform(kp, (num_users, k), dtype, -lim, lim)
        q = jax.random.uniform(kq, (num_items, k), dtype, -lim, lim)
        y = jax.random.uniform(ky, (num_items + 1, k), dtype, -lim, lim)
    elif init_method == "libmf":
        # LibMF's non-negative init, U(0, 1/sqrt(k)).  The positive common
        # component it induces is what concentrates significance in leading
        # latent dims (the paper's Fig. 7 distributions have mu > 0, and
        # Eq. 8 explicitly handles the asymmetric case) — the regime where
        # dynamic pruning keeps P_MAE <= 20% (EXPERIMENTS.md §Repro).
        lim = k ** -0.5
        p = jax.random.uniform(kp, (num_users, k), dtype, 0.0, lim)
        q = jax.random.uniform(kq, (num_items, k), dtype, 0.0, lim)
        y = jax.random.uniform(ky, (num_items + 1, k), dtype, 0.0, lim)
    else:
        raise ValueError(f"unknown init {init_method!r}")

    with_bias = variant in ("bias", "svdpp")
    return MFParams(
        p=p,
        q=q,
        user_bias=jnp.zeros((num_users, 1), dtype) if with_bias else None,
        item_bias=jnp.zeros((num_items, 1), dtype) if with_bias else None,
        global_mean=jnp.asarray(global_mean, dtype) if with_bias else None,
        implicit=y.at[num_items].set(0.0) if variant == "svdpp" else None,
    )


def params_from_flat(arrays: Dict[str, Any], prefix: str = "params__") -> MFParams:
    """Rebuild :class:`MFParams` from a flat ``{key: array}`` checkpoint
    payload (the ``params__p``-style keys the checkpointer's path flattening
    produces).  The single owner of that key mapping — the serving loader
    and the online delta folds both go through here."""

    def opt(name):
        key = prefix + name
        return jnp.asarray(arrays[key]) if key in arrays else None

    return MFParams(
        p=jnp.asarray(arrays[prefix + "p"]),
        q=jnp.asarray(arrays[prefix + "q"]),
        user_bias=opt("user_bias"),
        item_bias=opt("item_bias"),
        global_mean=opt("global_mean"),
        implicit=opt("implicit"),
    )


def _user_vector(
    params: MFParams, u: jax.Array, hist: Optional[jax.Array]
) -> jax.Array:
    """p_u, or SVD++'s p_u + |N(u)|^-1/2 * sum_{j in N(u)} y_j."""
    p_rows = params.p[u]
    if params.implicit is None or hist is None:
        return p_rows
    # hist: (B, H) item ids padded with num_items (the zero row of `implicit`).
    n_items = params.implicit.shape[0] - 1
    y_sum = jnp.sum(params.implicit[hist], axis=1)
    counts = jnp.sum((hist < n_items).astype(jnp.float32), axis=1, keepdims=True)
    return p_rows + y_sum * jax.lax.rsqrt(jnp.maximum(counts, 1.0))


def predict_pairs(
    params: MFParams,
    u: jax.Array,
    i: jax.Array,
    t_p: jax.Array | float = 0.0,
    t_q: jax.Array | float = 0.0,
    hist: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Pruned predictions for (u, i) pairs.  Returns (pred, pair_ranks)."""
    pu = _user_vector(params, u, hist)
    qi = params.q[i]
    r_u = effective_ranks(pu, t_p)
    r_i = effective_ranks(qi, t_q)
    k = pu.shape[-1]
    mask = rank_mask(jnp.minimum(r_u, r_i), k)
    pred = jnp.sum(pu.astype(jnp.float32) * qi.astype(jnp.float32) * mask, axis=-1)
    if params.user_bias is not None:
        pred = (
            pred
            + params.global_mean
            + params.user_bias[u, 0]
            + params.item_bias[i, 0]
        )
    return pred, jnp.minimum(r_u, r_i)


def predict_all_items(
    params: MFParams,
    u: jax.Array,
    t_p: jax.Array | float = 0.0,
    t_q: jax.Array | float = 0.0,
    *,
    use_kernel: bool = True,
    hist: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Serving / retrieval: score a user batch against *all* items.

    This is the paper's "matrix multiplication" stage at recommendation time
    and the hot path of the `retrieval_cand` shape — routed through the
    tile-ragged Pallas kernel.
    """
    pu = _user_vector(params, u, hist)
    r_u = effective_ranks(pu, t_p)
    r_i = effective_ranks(params.q, t_q)
    if use_kernel:
        scores = kops.pruned_matmul(
            pu, params.q, t_p, t_q, interpret=interpret
        )
    else:
        from repro.kernels import ref

        scores = ref.pruned_matmul_ref(pu, params.q, r_u, r_i)
    if params.user_bias is not None:
        scores = (
            scores
            + params.global_mean
            + params.user_bias[u]
            + params.item_bias[:, 0][None, :]
        )
    return scores


class MFOptState(NamedTuple):
    p: Dict[str, jax.Array]
    q: Dict[str, jax.Array]
    user_bias: Optional[Dict[str, jax.Array]]
    item_bias: Optional[Dict[str, jax.Array]]
    implicit: Optional[Dict[str, jax.Array]]


def init_opt_state(params: MFParams, opt: RowOptimizer) -> MFOptState:
    return MFOptState(
        p=opt.init(params.p),
        q=opt.init(params.q),
        user_bias=None if params.user_bias is None else opt.init(params.user_bias),
        item_bias=None if params.item_bias is None else opt.init(params.item_bias),
        implicit=None if params.implicit is None else opt.init(params.implicit),
    )


def _train_step(
    params: MFParams,
    opt_state: MFOptState,
    batch: Batch,
    t_p: jax.Array,
    t_q: jax.Array,
    lr: jax.Array,
    dim_mask: jax.Array,  # (k,) twin-learners / strategy mask
    *,
    opt: RowOptimizer,
    lam: float,
) -> Tuple[MFParams, MFOptState, Dict[str, jax.Array]]:
    """One minibatched, dynamically-pruned MF update (Algs. 2 + 3).

    Every (variant, optimizer) combination runs the same masked XLA
    formulation.  Duplicate (u, i) rows in a batch accumulate
    additively (summed per row on the device and written once, see
    ``optim.optimizers.add_rows``), the standard minibatch relaxation of the
    paper's sequential SGD.

    An optional ``batch["weight"]`` (B,) gates rows out of the update —
    gradients, bias/implicit updates, and metrics all scale by it (0 = row
    fully inert, fractional = importance weighting).  The weight multiplies
    the *update mask* and the metrics only — never the prediction, which
    must stay the full model output for the error (and thus the gradient
    direction) to be right.  NB: for the stateful-EMA optimizers
    (momentum/adadelta/adam) a zero-weight row still *writes back* its
    row's decayed state, the same caveat duplicate rows already carry —
    which is why the online updater chunks instead of padding.
    """
    u, i, r = batch["user"], batch["item"], batch["rating"].astype(jnp.float32)
    hist = batch.get("hist")
    weight = batch.get("weight")
    k = params.p.shape[-1]

    pu = _user_vector(params, u, hist)
    qi = params.q[i]
    r_u = effective_ranks(pu, t_p)
    r_i = effective_ranks(qi, t_q)
    pair_ranks = jnp.minimum(r_u, r_i)
    pred_mask = rank_mask(pair_ranks, k) * dim_mask[None, :]
    w = (
        jnp.ones_like(r) if weight is None else weight.astype(jnp.float32)
    )
    mask = pred_mask * w[:, None]  # gates updates; predictions use pred_mask
    # an occurrence with an all-zero mask adds exact zeros: no row is written
    # for it, and the counters leave it out (the same sort as the writes)
    live = jnp.any(mask != 0, axis=-1)
    rows = (
        rows_written(u, live, params.p.shape[0]) / u.shape[0],
        rows_written(i, live, params.q.shape[0]) / i.shape[0],
    )

    pred = jnp.sum(
        pu.astype(jnp.float32) * qi.astype(jnp.float32) * pred_mask, axis=-1
    )
    if params.user_bias is not None:
        pred = (
            pred
            + params.global_mean
            + params.user_bias[u, 0]
            + params.item_bias[i, 0]
        )
    err = r - pred

    # Gradients of 0.5*err^2 + 0.5*lam*||.||^2 wrt the gathered rows; the
    # paper's update p += lr*(err*q - lam*p) is descent on exactly this.
    # SVD++ (Koren 2008, section 4) penalises p_u itself, not the user
    # vector pu = p_u + |N(u)|^-1/2 sum y_j that the error and g_q use.
    g_p = (lam * params.p[u] - err[:, None] * qi).astype(jnp.float32)
    g_q = (lam * qi - err[:, None] * pu).astype(jnp.float32)

    new_p, st_p = opt.apply_rows(params.p, opt_state.p, u, g_p, mask, lr)
    new_q, st_q = opt.apply_rows(params.q, opt_state.q, i, g_q, mask, lr)
    new_params = params._replace(p=new_p, q=new_q)
    new_state = opt_state._replace(p=st_p, q=st_q)

    if params.user_bias is not None:
        w_col = w[:, None]
        g_bu = (lam * params.user_bias[u] - err[:, None]).astype(jnp.float32)
        g_bi = (lam * params.item_bias[i] - err[:, None]).astype(jnp.float32)
        new_bu, st_bu = opt.apply_rows(
            params.user_bias, opt_state.user_bias, u, g_bu, w_col, lr
        )
        new_bi, st_bi = opt.apply_rows(
            params.item_bias, opt_state.item_bias, i, g_bi, w_col, lr
        )
        new_params = new_params._replace(user_bias=new_bu, item_bias=new_bi)
        new_state = new_state._replace(user_bias=st_bu, item_bias=st_bi)

    if params.implicit is not None and hist is not None:
        # dL/dy_j = lam*y_j - err * q_i / sqrt(|N(u)|) for each j in N(u),
        # masked by the rating's mask; padding slots add nothing
        n_items = params.implicit.shape[0] - 1
        counts = jnp.sum((hist < n_items).astype(jnp.float32), axis=1, keepdims=True)
        coef = err[:, None] * jax.lax.rsqrt(jnp.maximum(counts, 1.0))
        g_y = lam * params.implicit[hist] - (coef * qi)[:, None, :]
        flat_idx = hist.reshape(-1)
        flat_mask = jnp.repeat(mask, hist.shape[1], axis=0) * (
            flat_idx < n_items
        ).astype(jnp.float32)[:, None]
        new_y, st_y = opt.apply_rows(
            params.implicit, opt_state.implicit, flat_idx,
            g_y.reshape(-1, k).astype(jnp.float32), flat_mask, lr,
        )
        new_params = new_params._replace(implicit=new_y)
        new_state = new_state._replace(implicit=st_y)
        flat_live = jnp.any(flat_mask != 0, axis=-1)
        rows = rows + (
            rows_written(flat_idx, flat_live, n_items + 1)
            / jnp.maximum(jnp.sum(flat_live), 1),
        )

    return new_params, new_state, _step_metrics(err, w, pair_ranks, k, rows)


def _step_metrics(err, w, pair_ranks, k, rows) -> Dict[str, jax.Array]:
    """A step's weighted means, and the share of the batch's ids that are
    rows written, user side and item side (and SVD++'s rows of the
    implicit table written over its live history occurrences)."""
    denom = jnp.maximum(jnp.sum(w), 1e-9)  # weighted mean, not deflated
    names = ("user_rows_share", "item_rows_share", "implicit_rows_share")
    return {
        "abs_err": jnp.sum(jnp.abs(err) * w) / denom,
        "work_fraction": jnp.sum(pair_ranks.astype(jnp.float32) * w)
        / (denom * k),
        **dict(zip(names, rows)),
    }


train_step = jax.jit(_train_step, static_argnames=("opt", "lam"))


def _eval_mae(
    params: MFParams,
    batch: Batch,
    t_p: jax.Array,
    t_q: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Sum |err| and count over a (possibly weight-masked) eval batch."""
    pred, _ = predict_pairs(
        params, batch["user"], batch["item"], t_p, t_q, batch.get("hist")
    )
    w = batch.get("weight", jnp.ones_like(pred))
    abs_err = jnp.abs(batch["rating"].astype(jnp.float32) - pred) * w
    return jnp.sum(abs_err), jnp.sum(w)


eval_mae = jax.jit(_eval_mae)


# ---------------------------------------------------------------------------
# Epoch-compiled training: one donated lax.scan per epoch
# ---------------------------------------------------------------------------


def _epoch_scan(step_fn, params, opt_state, batches):
    """``lax.scan`` of ``step_fn`` over packed ``(steps, B)`` batch arrays.

    Metrics accumulate on device (sum of per-batch means, divided once at the
    end — identical to a per-batch loop of ``step_fn``) so an epoch
    costs exactly one host sync, taken by the *caller* when it fetches the
    returned scalars.
    """
    steps = jax.tree_util.tree_leaves(batches)[0].shape[0]

    sums0 = jax.tree.map(
        lambda m: jnp.zeros(m.shape, m.dtype),
        jax.eval_shape(
            lambda: step_fn(
                params, opt_state, jax.tree.map(lambda b: b[0], batches)
            )[2]
        ),
    )

    def body(carry, batch):
        p, s, sums = carry
        p, s, m = step_fn(p, s, batch)
        return (p, s, {key: sums[key] + m[key] for key in sums}), None

    (new_params, new_state, sums), _ = jax.lax.scan(
        body, (params, opt_state, sums0), batches
    )
    denom = jnp.float32(max(steps, 1))
    return new_params, new_state, {key: v / denom for key, v in sums.items()}


@functools.partial(
    jax.jit,
    static_argnames=("opt", "lam"),
    donate_argnums=(0, 1),
)
def train_epoch_scan(
    params: MFParams,
    opt_state: MFOptState,
    batches: Batch,       # each value (steps, B, ...) — data/loader.PackedRatings
    t_p: jax.Array,
    t_q: jax.Array,
    lr: jax.Array,
    dim_mask: jax.Array,
    hist: Optional[jax.Array] = None,   # (m, H) device-resident SVD++ history
    *,
    opt: RowOptimizer,
    lam: float,
) -> Tuple[MFParams, MFOptState, Dict[str, jax.Array]]:
    """A whole epoch as ONE compiled, donated computation.

    Semantically a fold of :func:`train_step` over the packed batches —
    ``train_step`` stays the single-step owner (the online updater calls it
    directly); this is the same body traced once into a ``lax.scan``, so an
    epoch pays no per-step dispatch, upload or sync.
    ``donate_argnums=(0, 1)`` lets XLA update params and optimizer state in
    place across the epoch.  The SVD++ history table is passed whole and
    gathered per step on device, instead of being packed into (steps, B, H)
    batch arrays.
    """

    def step(p, s, batch):
        if hist is not None:
            batch = dict(batch, hist=hist[batch["user"]])
        return _train_step(
            p, s, batch, t_p, t_q, lr, dim_mask, opt=opt, lam=lam
        )

    return _epoch_scan(step, params, opt_state, batches)


@jax.jit
def eval_epoch_scan(
    params: MFParams,
    batches: Batch,       # each value (steps, B, ...), weight-padded tail
    t_p: jax.Array,
    t_q: jax.Array,
    hist: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sum |err| and weighted count over pre-packed eval batches — the
    :func:`eval_mae` treatment of a whole pass, fetched once."""

    def body(carry, batch):
        tot, cnt = carry
        if hist is not None:
            batch = dict(batch, hist=hist[batch["user"]])
        s, c = _eval_mae(params, batch, t_p, t_q)
        return (tot + s, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), batches
    )
    return tot, cnt


@functools.partial(jax.jit, static_argnames=("topk",))
def eval_ranking_epoch_scan(
    params: MFParams,
    batches: Batch,       # repro.eval.ranking.pack_ranking_batches output
    t_p: jax.Array,
    t_q: jax.Array,
    hist: Optional[jax.Array] = None,
    *,
    topk: int,
) -> Dict[str, jax.Array]:
    """Ranking-metrics variant of :func:`eval_epoch_scan`: HR@K / NDCG@K /
    recall@K sums over pre-packed user batches, one compiled scan.

    Each step scores its user batch against the full catalog with the
    masked (rank-truncated) formulation — the same math the serving layouts
    bake in, so at equal thresholds the resulting rankings are the engine's
    — takes ``lax.top_k``, and folds the batch through
    :func:`repro.eval.ranking.ranking_counts`.  The per-user additive
    constant (user bias + global mean) is omitted: it never changes a
    ranking.  Item ranks reduce once outside the scan.  ``batches`` comes
    from :func:`repro.eval.ranking.pack_ranking_batches`; divide the metric
    sums by ``weight_sum`` for means (``RankingReport`` semantics).
    """
    from repro.eval.ranking import ranking_counts

    k = params.p.shape[1]
    r_i = effective_ranks(params.q, t_q)
    qm = params.q.astype(jnp.float32) * rank_mask(r_i, k)
    item_bias = (
        None if params.item_bias is None
        else params.item_bias[:, 0].astype(jnp.float32)
    )

    def body(carry, batch):
        u = batch["user"]
        h = None if hist is None else hist[u]
        pu = _user_vector(params, u, h)
        r_u = effective_ranks(pu, t_p)
        pm = pu.astype(jnp.float32) * rank_mask(r_u, k)
        scores = jnp.dot(
            pm, qm.T, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        if item_bias is not None:
            scores = scores + item_bias[None, :]
        _, idx = jax.lax.top_k(scores, topk)
        counts = ranking_counts(
            idx, batch["relevant"], batch["n_valid"], batch.get("weight")
        )
        return (
            {key: carry[key] + counts[key] for key in carry},
            None,
        )

    init = {
        key: jnp.zeros((), jnp.float32)
        for key in ("hr_sum", "ndcg_sum", "recall_sum", "weight_sum")
    }
    sums, _ = jax.lax.scan(body, init, batches)
    return sums


# ---------------------------------------------------------------------------
# Owner-compute distributed step (§Perf iteration for the paper's model)
# ---------------------------------------------------------------------------


def _check_owner_compute_opt(opt_name: str) -> None:
    if opt_name not in ("adagrad", "sgd"):
        raise ValueError(
            "the owner-compute step implements sgd and adagrad only, got "
            f"{opt_name!r}"
        )


def init_error_feedback_state(
    params: MFParams, opt_state: MFOptState, mesh=None
) -> MFOptState:
    """Attach int8 error-feedback residual tables to ``opt_state``.

    ``grad_compression="int8_ef"`` keeps, per *sender*, the running
    quantization residual of each collective payload and folds it into the
    next step's transmission (EF-SGD: the optimizer trajectory converges as
    if the links were full-precision).  Two residual tables, one per
    compressed collective, shaped so each mesh rank owns exactly its own
    sender state:

    * ``opt_state.p["ef_psum"]``: ``(m, n_model * k)`` over ``P(dp,
      "model")`` — each model rank's untransmitted part of the p-gradient
      psum, keyed by user row.
    * ``opt_state.q["ef_gather"]``: ``(n, n_dp * k)`` over ``P("model",
      dp)`` — each data rank's untransmitted part of the q-delta
      all-gather, keyed by item row.
    """
    mesh = mesh if mesh is not None else jax.sharding.get_abstract_mesh()
    if mesh.empty:
        raise ValueError(
            "init_error_feedback_state needs a mesh: pass mesh= or enter a "
            "jax.sharding.set_mesh(...) context"
        )
    n_dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n_dp *= mesh.shape[a]
    n_model = mesh.shape["model"]
    m, k = params.p.shape
    n = params.q.shape[0]
    return opt_state._replace(
        p={**opt_state.p, "ef_psum": jnp.zeros((m, n_model * k), jnp.float32)},
        q={**opt_state.q, "ef_gather": jnp.zeros((n, n_dp * k), jnp.float32)},
    )


def _first_occurrence(keys: jax.Array) -> jax.Array:
    """(B,) bool, True at the first position of each distinct key."""
    order = jnp.argsort(keys, stable=True)
    sk = keys[order]
    head = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    return jnp.zeros(keys.shape, bool).at[order].set(head)


def train_step_shard_map(
    params: MFParams,
    opt_state: MFOptState,
    batch: Batch,
    t_p: jax.Array,
    t_q: jax.Array,
    *,
    lr: float,
    lam: float,
    opt_name: str = "adagrad",
    eps: float = 1e-8,
    grad_compression: str = "none",
    mesh=None,
) -> Tuple[MFParams, MFOptState, Dict[str, jax.Array]]:
    """DP-MF minibatch step with owner-compute collectives (FunkSVD only).

    The XLA-SPMD lowering of :func:`train_step` all-reduces the gathered
    (B, k) item rows *and* the full (n, k) item-gradient scatter across the
    mesh (~7 GB/device/step at the dpmf train_1m shape).  This formulation
    exploits the sharding contract instead:

      * user rows P are sharded over the data axes; the data pipeline routes
        each rating to its user's shard (standard row-wise sharding), so all
        P traffic is local;
      * item rows Q are sharded over "model"; each model rank computes the
        *partial* masked dot for the ratings whose item it owns (other ranks
        contribute exact zeros, because a zero row has effective rank 0);
      * ONE psum of the (B_loc,) partial predictions and ONE psum of the
        (B_loc, k) masked p-deltas cross the links; the q update never
        leaves its owner.

    ``grad_compression="int8"`` int8-quantizes the p-gradient psum and the
    q-delta all-gather payloads (4x fewer bytes on the dominant collectives;
    per-tensor scales psum'd alongside).  Quantization error is bounded by
    scale/2 per element.
    ``"int8_ef"`` adds per-sender error feedback: each rank keeps the
    residual its quantizer dropped (``init_error_feedback_state`` tables in
    ``opt_state``) and folds it into the next transmission of the same row
    — the EF-SGD recipe that keeps long-run convergence at fp32 quality.
    A row's residual rides on exactly one of its live (weight > 0)
    occurrences in the batch: folded into every duplicate it would be sent
    D times while banked once, and the residual would grow by a factor
    (1 - D) per step — divergence on a skewed (Zipf) item stream.

    Collectives drop from O(n*k + B*k) all-reduce bytes to O(B_loc*k) —
    measured in EXPERIMENTS.md §Perf.  Semantics are identical to
    :func:`train_step` (same masked Alg. 2/3 math; duplicate rows
    accumulate), including the optional ``batch["weight"]`` update gate —
    zero-weight rows are fully inert, which is what lets the online
    updater's shard router pad per-shard buckets.
    """
    from jax.sharding import PartitionSpec as P

    mesh = mesh if mesh is not None else jax.sharding.get_abstract_mesh()
    if mesh.empty:
        raise ValueError(
            "train_step_shard_map needs a mesh: pass mesh= or enter a "
            "jax.sharding.set_mesh(...) context"
        )
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    n_model = mesh.shape["model"]
    m_loc = params.p.shape[0] // n_dp
    n_loc = params.q.shape[0] // n_model
    k = params.p.shape[1]
    _check_owner_compute_opt(opt_name)
    adagrad = opt_name == "adagrad"
    gc = grad_compression
    if gc not in ("none", "int8", "int8_ef"):
        raise ValueError(f"grad_compression must be none|int8|int8_ef, got {gc!r}")

    def body(p_blk, q_blk, acc_p, acc_q, ef_p, ef_q, u, i, r, w, t_p, t_q):
        # block-local coordinates
        dp_idx = jnp.int32(0)
        stride = 1
        for a in reversed(dp):
            dp_idx = dp_idx + jax.lax.axis_index(a) * stride
            stride *= mesh.shape[a]
        u_loc = u - dp_idx * m_loc          # pipeline guarantees ownership
        m_idx = jax.lax.axis_index("model")
        off_i = m_idx * n_loc
        is_local = (i >= off_i) & (i < off_i + n_loc)
        i_loc = jnp.clip(i - off_i, 0, n_loc - 1)

        p_rows = p_blk[u_loc].astype(jnp.float32)          # (B_loc, k)
        q_rows = jnp.where(
            is_local[:, None], q_blk[i_loc].astype(jnp.float32), 0.0
        )

        r_u = effective_ranks(p_rows, t_p)
        r_i = effective_ranks(q_rows, t_q)  # 0 on non-owners (zero rows)
        mask_p = rank_mask(r_u, k)
        mask_q = rank_mask(r_i, k)
        pair_mask = mask_p * mask_q

        # Everything is gated by ownership: at t_q == 0 a zero (non-owner)
        # row has effective rank k, so relying on rank-masking alone would
        # multiply the lambda term by n_model through the psum.
        own = is_local[:, None].astype(jnp.float32)
        pred = jax.lax.psum(
            jnp.sum(p_rows * q_rows * pair_mask, axis=-1) * is_local, "model"
        )
        err = r.astype(jnp.float32) - pred
        wv = w.astype(jnp.float32)[:, None]
        # train_step's live occurrences (mask = pair mask x weight), read on
        # each item's owner: the rows written, identical on every rank
        live = jax.lax.psum(
            jnp.any(own * pair_mask * wv != 0, axis=-1).astype(jnp.int32),
            "model",
        ) > 0
        live_p, live_q = live, live & is_local

        # p gradient: assembled on the item owner (it holds q), then one psum.
        # Both gradients carry the full pair mask (Alg. 3 truncates the
        # entire update at min(r_u, r_i)) and the row weight — matching
        # train_step's ``mask = pred_mask * w`` gate exactly.
        g_p_partial = own * pair_mask * wv * (
            lam * p_rows - err[:, None] * q_rows
        )
        if gc == "int8_ef":
            # Sender-side error feedback on the psum: fold this rank's
            # residual for these user rows into the payload, quantize to a
            # mesh-common scale (exact int8 summation), and bank what the
            # quantizer dropped back into the residual table.  The residual
            # update is a scatter-ADD of (partial - transmitted), so
            # duplicate batch rows stay deterministic; the old residual rides
            # on one live occurrence of the row only.
            carrier = _first_occurrence(jnp.where(wv[:, 0] > 0, u_loc, m_loc))
            live_p = live_p | carrier   # a carried residual is an update too
            resid = ef_p[u_loc] * carrier[:, None]
            target = g_p_partial + resid
            local_max = jnp.max(jnp.abs(target))
            scale = jnp.maximum(
                jax.lax.pmax(local_max, "model"), 1e-12
            ) / 127.0
            q8 = jnp.clip(jnp.round(target / scale), -127, 127).astype(jnp.int8)
            recon = q8.astype(jnp.float32) * scale
            g_p = jax.lax.psum(q8.astype(jnp.int32), "model").astype(
                jnp.float32
            ) * scale
            ef_p = ef_p.at[u_loc].add(g_p_partial - recon)
        elif gc == "int8":
            from repro.distributed.compression import compressed_psum

            g_p = compressed_psum(g_p_partial, "model")
        else:
            g_p = jax.lax.psum(g_p_partial, "model")
        g_q = own * pair_mask * wv * (lam * q_rows - err[:, None] * p_rows)
        safe_i = jnp.where(is_local, i_loc, 0)

        if adagrad:
            # The second ``* wv`` mirrors RowOptimizer.apply_rows, whose
            # delta multiplies the mask again after the accumulator update —
            # a no-op for 0/1 weights, required for fractional ones.  (The
            # pair-mask part of that second mask is already folded into g.)
            acc_p_rows = acc_p[u_loc] + g_p * g_p
            dp_rows = -lr * g_p / jnp.sqrt(acc_p_rows + eps) * wv
            p_blk, acc_p = add_rows(
                (p_blk, acc_p), u_loc, (dp_rows, g_p * g_p), live_p
            )
            acc_q_rows = acc_q[safe_i] + g_q * g_q
            dq_rows = jnp.where(
                is_local[:, None],
                -lr * g_q / jnp.sqrt(acc_q_rows + eps) * wv,
                0.0,
            )
        else:  # plain SGD
            dp_rows = -lr * g_p
            dq_rows = -lr * g_q
            (p_blk,) = add_rows((p_blk,), u_loc, (dp_rows,), live_p)

        # Q is replicated along the data axes, but each data shard computed
        # deltas only for ITS ratings: all-gather the sparse (B_loc, k) delta
        # rows (+ indices, + adagrad g^2) so every replica applies the same
        # total update.  This moves B*k delta floats instead of the dense
        # (n, k) gradient all-reduce XLA emits for train_step.
        if dp:
            if gc in ("int8", "int8_ef"):
                from repro.distributed.compression import (
                    dequantize_int8,
                    quantize_int8,
                )

                if gc == "int8_ef":
                    # residual rows only exist for items this model rank
                    # owns; non-owner rows transmit exact zeros as before,
                    # and one live occurrence of an item carries its residual
                    carrier = _first_occurrence(
                        jnp.where(is_local & (w > 0), safe_i, n_loc)
                    )
                    live_q = live_q | (carrier & is_local)
                    payload = jnp.where(
                        is_local[:, None],
                        dq_rows + ef_q[safe_i] * carrier[:, None],
                        0.0,
                    )
                else:
                    payload = dq_rows
                q8, scale = quantize_int8(payload)
                gat_q8 = jax.lax.all_gather(q8, dp)
                gat_scale = jax.lax.all_gather(scale, dp)
                gat_dq = dequantize_int8(
                    gat_q8, gat_scale.reshape((-1,) + (1,) * q8.ndim)
                ).reshape(-1, k)
                if gc == "int8_ef":
                    recon = dequantize_int8(q8, scale)
                    ef_q = ef_q.at[safe_i].add(
                        jnp.where(is_local[:, None], dq_rows - recon, 0.0)
                    )
            else:
                gat_dq = jax.lax.all_gather(dq_rows, dp).reshape(-1, k)
            gat_idx = jax.lax.all_gather(safe_i, dp).reshape(-1)
            gat_live = jax.lax.all_gather(live_q, dp).reshape(-1)
            if adagrad:
                gat_g2 = jax.lax.all_gather(g_q * g_q, dp).reshape(-1, k)
                q_blk, acc_q = add_rows(
                    (q_blk, acc_q), gat_idx, (gat_dq, gat_g2), gat_live
                )
            else:
                (q_blk,) = add_rows((q_blk,), gat_idx, (gat_dq,), gat_live)
        elif adagrad:
            q_blk, acc_q = add_rows(
                (q_blk, acc_q), safe_i, (dq_rows, g_q * g_q), live_q
            )
        else:
            (q_blk,) = add_rows((q_blk,), safe_i, (dq_rows,), live_q)

        # Weighted epoch metrics, summed on device (err and w are identical
        # on every model rank, so only the data axes need a psum).
        r_i_owner = jax.lax.psum(r_i * is_local, "model")
        wf = w.astype(jnp.float32)
        w_sum = jnp.sum(wf)
        abs_sum = jnp.sum(jnp.abs(err) * wf)
        work_sum = jnp.sum(
            jnp.minimum(r_u, r_i_owner).astype(jnp.float32) * wf
        )
        if dp:
            w_sum = jax.lax.psum(w_sum, dp)
            abs_sum = jax.lax.psum(abs_sum, dp)
            work_sum = jax.lax.psum(work_sum, dp)
        denom = jnp.maximum(w_sum, 1e-9)
        abs_err = abs_sum / denom
        work = work_sum / (denom * k)
        return p_blk, q_blk, acc_p, acc_q, ef_p, ef_q, abs_err[None], work[None]

    acc_p_in = opt_state.p.get("acc") if adagrad else params.p
    acc_q_in = opt_state.q.get("acc") if adagrad else params.q
    if gc == "int8_ef":
        ef_p_in = opt_state.p.get("ef_psum")
        ef_q_in = opt_state.q.get("ef_gather")
        if ef_p_in is None or ef_q_in is None:
            raise ValueError(
                "grad_compression='int8_ef' needs the residual tables: call "
                "mf.init_error_feedback_state(params, opt_state, mesh) first"
            )
    else:
        # placeholder operands so every mode shares one shard_map signature;
        # (n_dp, n_model)-shaped zeros shard to (1, 1) blocks — negligible
        ef_p_in = jnp.zeros((n_dp, n_model), jnp.float32)
        ef_q_in = jnp.zeros((n_model, n_dp), jnp.float32)

    weight = batch.get("weight")
    if weight is None:
        weight = jnp.ones_like(batch["rating"], dtype=jnp.float32)
    new_p, new_q, acc_p, acc_q, ef_p_out, ef_q_out, abs_err, work = (
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(dp, None), P("model", None), P(dp, None), P("model", None),
                P(dp, "model"), P("model", dp),
                P(dp), P(dp), P(dp), P(dp), P(), P(),
            ),
            out_specs=(
                P(dp, None), P("model", None), P(dp, None), P("model", None),
                P(dp, "model"), P("model", dp),
                P(None), P(None),
            ),
            check_vma=False,
        )(
            params.p, params.q, acc_p_in, acc_q_in, ef_p_in, ef_q_in,
            batch["user"], batch["item"], batch["rating"].astype(jnp.float32),
            weight.astype(jnp.float32),
            jnp.asarray(t_p, jnp.float32), jnp.asarray(t_q, jnp.float32),
        )
    )
    new_params = params._replace(p=new_p, q=new_q)
    if adagrad or gc == "int8_ef":
        p_state = dict(opt_state.p)
        q_state = dict(opt_state.q)
        if adagrad:
            p_state["acc"] = acc_p
            q_state["acc"] = acc_q
        if gc == "int8_ef":
            p_state["ef_psum"] = ef_p_out
            q_state["ef_gather"] = ef_q_out
        new_state = opt_state._replace(p=p_state, q=q_state)
    else:
        new_state = opt_state
    metrics = {"abs_err": abs_err[0], "work_fraction": work[0]}
    return new_params, new_state, metrics


@functools.partial(
    jax.jit,
    static_argnames=(
        "lr", "lam", "opt_name", "eps", "grad_compression", "mesh",
    ),
    donate_argnums=(0, 1),
)
def _train_epoch_scan_shard_map(
    params, opt_state, batches, t_p, t_q,
    *, lr, lam, opt_name, eps, grad_compression, mesh,
):
    def step(p, s, batch):
        return train_step_shard_map(
            p, s, batch, t_p, t_q, lr=lr, lam=lam, opt_name=opt_name,
            eps=eps, grad_compression=grad_compression, mesh=mesh,
        )

    return _epoch_scan(step, params, opt_state, batches)


def train_epoch_scan_shard_map(
    params: MFParams,
    opt_state: MFOptState,
    batches: Batch,
    t_p: jax.Array | float,
    t_q: jax.Array | float,
    *,
    lr: float,
    lam: float,
    opt_name: str = "adagrad",
    eps: float = 1e-8,
    grad_compression: str = "none",
    mesh=None,
) -> Tuple[MFParams, MFOptState, Dict[str, jax.Array]]:
    """Epoch-compiled multi-device training: the owner-compute
    :func:`train_step_shard_map` folded through the same donated
    ``lax.scan`` as :func:`train_epoch_scan`, so single-device and sharded
    training (and the online updater's distributed refresh) share one epoch
    implementation.  ``batches`` follows the same ownership contract as the
    single step: every rating's user must live on its data shard's P block.
    """
    _check_owner_compute_opt(opt_name)
    mesh = mesh if mesh is not None else jax.sharding.get_abstract_mesh()
    if mesh.empty:
        raise ValueError(
            "train_epoch_scan_shard_map needs a mesh: pass mesh= or enter a "
            "jax.sharding.set_mesh(...) context"
        )
    return _train_epoch_scan_shard_map(
        params, opt_state, batches,
        jnp.asarray(t_p, jnp.float32), jnp.asarray(t_q, jnp.float32),
        lr=float(lr), lam=float(lam), opt_name=opt_name, eps=float(eps),
        grad_compression=str(grad_compression), mesh=mesh,
    )
