"""Public jit'd wrappers around the Pallas kernels.

Handles padding to block multiples, interpret-mode selection (the container
is CPU-only; TPU is the target), and instrumentation of tile-level skipped
work.  All wrappers are shape-polymorphic at the Python level and fixed-shape
under jit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.ranks import effective_ranks, rank_mask
from repro.kernels import ref
from repro.kernels.fused_mf_sgd import fused_mf_sgd_padded
from repro.kernels.pruned_matmul import pruned_matmul_padded
from repro.kernels.pruned_topk import (
    TOPK_BLOCK_K,
    TOPK_BLOCK_M,
    TOPK_BLOCK_N,
    pruned_topk_padded,
)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, multiple: int, axis: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def pruned_matmul(
    p: jax.Array,
    q: jax.Array,
    t_p: jax.Array | float,
    t_q: jax.Array | float,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """All-pairs early-stopped product ``(m, k) x (n, k) -> (m, n)``.

    Ranks are derived from the current factor values (dynamic pruning).  With
    ``use_kernel=False`` falls back to the XLA masked formulation — same
    numerics, no block skipping (used on meshes where the kernel is not the
    bottleneck and for the dry-run's SPMD partitioning).
    """
    r_u = effective_ranks(p, t_p)
    r_i = effective_ranks(q, t_q)
    if not use_kernel:
        return ref.pruned_matmul_ref(p, q, r_u, r_i, out_dtype=out_dtype)

    if interpret is None:
        interpret = _default_interpret()
    m, n = p.shape[0], q.shape[0]
    pp = _pad_to(_pad_to(p, block_m, 0), block_k, 1)
    qp = _pad_to(_pad_to(q, block_n, 0), block_k, 1)
    rup = _pad_to(r_u[:, None].astype(jnp.int32), block_m, 0)
    rip = _pad_to(r_i[:, None].astype(jnp.int32), block_n, 0)
    out = pruned_matmul_padded(
        pp,
        qp,
        rup,
        rip,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("topk",))
def stream_topk_tiles(pm, q_tiles, b_tiles, offs, *, topk):
    """Streaming XLA top-k over pre-tiled item factors: scan item tiles,
    folding each (m, block_n) score tile into a running (m, topk) buffer
    with ``lax.top_k`` over the concatenation.

    ``pm`` is the rank-masked user block (m, k); ``q_tiles`` the rank-masked
    item factors (tiles, block_n, k); ``b_tiles`` per-item additive biases
    with ``-inf`` on padding rows (so they can never be selected); ``offs``
    each tile's first global item index.  Peak live memory is
    O(m * (topk + block_n)) — the (m, n) score matrix is never materialized.
    Concatenating the running buffer FIRST makes ``lax.top_k``'s
    lowest-index tie preference resolve toward earlier item tiles, matching
    the stable dense argsort oracle; the score matmul is pinned to HIGHEST
    precision like the oracle's (a TPU's default float32 matmul is not
    float32-accurate).  Shared by :func:`pruned_topk`
    (``use_kernel=False``) and the serving engine's local + sharded paths —
    the tie-order subtlety lives in exactly one place.
    """
    m = pm.shape[0]
    block_n = q_tiles.shape[1]

    def merge(carry, tile):
        run_s, run_i = carry
        qt, bt, off = tile
        s = jnp.dot(pm, qt.T, precision=jax.lax.Precision.HIGHEST) + bt[None, :]
        gidx = off + jnp.arange(block_n, dtype=jnp.int32)
        cand_s = jnp.concatenate([run_s, s], axis=1)
        cand_i = jnp.concatenate(
            [run_i, jnp.broadcast_to(gidx, (m, block_n))], axis=1
        )
        new_s, sel = jax.lax.top_k(cand_s, topk)
        return (new_s, jnp.take_along_axis(cand_i, sel, axis=1)), None

    init = (
        jnp.full((m, topk), -jnp.inf, jnp.float32),
        jnp.zeros((m, topk), jnp.int32),
    )
    (scores, idx), _ = jax.lax.scan(merge, init, (q_tiles, b_tiles, offs))
    return scores, idx


def tile_catalog(qm, bias, block_n: int):
    """Pad + reshape rank-masked item factors into the streaming layout:
    ``(tiles, block_n, k)`` factors, ``(tiles, block_n)`` biases with -inf
    on padding rows, ``(tiles,)`` global offsets."""
    n, k = qm.shape
    pad = (-n) % block_n
    qm_p = jnp.pad(qm, ((0, pad), (0, 0)))
    bias_p = jnp.pad(bias, (0, pad), constant_values=-jnp.inf)
    tiles = (n + pad) // block_n
    return (
        qm_p.reshape(tiles, block_n, k),
        bias_p.reshape(tiles, block_n),
        jnp.arange(tiles, dtype=jnp.int32) * block_n,
    )


def _pruned_topk_scan(p, q, r_u, r_i, item_bias, *, topk, block_n):
    k = p.shape[1]
    pm = p.astype(jnp.float32) * rank_mask(r_u, k)
    qm = q.astype(jnp.float32) * rank_mask(r_i, k)
    q_tiles, b_tiles, offs = tile_catalog(
        qm, item_bias.astype(jnp.float32), block_n
    )
    return stream_topk_tiles(pm, q_tiles, b_tiles, offs, topk=topk)


def pad_catalog_for_topk_kernel(
    q, r_i, item_bias, *, block_n: int = TOPK_BLOCK_N,
    block_k: int = TOPK_BLOCK_K,
):
    """Item-side operands of ``pruned_topk_padded``: raw factors, ranks, and
    biases padded to the kernel's block multiples.  The single definition of
    the kernel's catalog-layout contract — the serving engine precomputes
    this once at load time and :func:`pruned_topk` builds it per call."""
    n = q.shape[0]
    bias = item_bias if item_bias is not None else jnp.zeros((n,), jnp.float32)
    return (
        _pad_to(_pad_to(q, block_n, 0), block_k, 1),
        _pad_to(r_i[:, None].astype(jnp.int32), block_n, 0),
        _pad_to(bias.astype(jnp.float32)[:, None], block_n, 0),
    )


def pad_users_for_topk_kernel(
    p, r_u, *, block_m: int = TOPK_BLOCK_M, block_k: int = TOPK_BLOCK_K
):
    """User-side operands of ``pruned_topk_padded`` (see above)."""
    return (
        _pad_to(_pad_to(p, block_m, 0), block_k, 1),
        _pad_to(r_u[:, None].astype(jnp.int32), block_m, 0),
    )


def pruned_topk(
    p: jax.Array,
    q: jax.Array,
    t_p: jax.Array | float,
    t_q: jax.Array | float,
    topk: int,
    *,
    item_bias: jax.Array | None = None,
    block_m: int = TOPK_BLOCK_M,
    block_n: int = TOPK_BLOCK_N,
    block_k: int = TOPK_BLOCK_K,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Top-k pruned scores per user row: ``(m, k) x (n, k) -> 2 x (m, topk)``.

    The serving hot path.  Returns ``(scores, item_indices)`` identical to
    scoring everything and argsorting (``ref.pruned_topk_ref``) but without
    materializing the (m, n) score matrix: the Pallas kernel keeps a running
    top-k in VMEM across item tiles; ``use_kernel=False`` selects the
    streaming ``lax.top_k``-merge formulation (the production CPU path).
    """
    n = q.shape[0]
    if not 0 < topk <= n:
        raise ValueError(f"topk must be in [1, {n}], got {topk}")
    r_u = effective_ranks(p, t_p)
    r_i = effective_ranks(q, t_q)

    if not use_kernel:
        bias = item_bias if item_bias is not None else jnp.zeros((n,), jnp.float32)
        return _pruned_topk_scan(
            p, q, r_u, r_i, bias, topk=topk, block_n=block_n
        )

    if interpret is None:
        interpret = _default_interpret()
    m = p.shape[0]
    pp, rup = pad_users_for_topk_kernel(p, r_u, block_m=block_m, block_k=block_k)
    qp, rip, biasp = pad_catalog_for_topk_kernel(
        q, r_i, item_bias, block_n=block_n, block_k=block_k
    )
    scores, idx, _ = pruned_topk_padded(
        pp, qp, rup, rip, biasp,
        topk=topk,
        n_items=n,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        interpret=interpret,
    )
    return scores[:m, :topk], idx[:m, :topk]


def fused_mf_sgd(
    p_rows: jax.Array,
    q_rows: jax.Array,
    ratings: jax.Array,
    t_p: jax.Array | float,
    t_q: jax.Array | float,
    *,
    lr: float,
    lam: float,
    bias_u: jax.Array | None = None,
    bias_i: jax.Array | None = None,
    global_mean: jax.Array | float = 0.0,
    weight: jax.Array | None = None,
    block_b: int = 256,
    interpret: bool | None = None,
    use_kernel: bool = True,
):
    """Fused Alg. 2 + Alg. 3 over a batch of gathered rows.

    Returns ``(new_p_rows, new_q_rows, new_bias_u, new_bias_i, err)`` with
    ``err`` shaped (B,); the bias outputs are None when the inputs are.
    Optional per-row biases + global mean fold into the prediction (BiasSVD)
    and an optional ``weight`` column gates the updates — both run inside
    the kernel, so the biased/weighted cases share the fused path.
    """
    t_p = jnp.asarray(t_p, jnp.float32)
    t_q = jnp.asarray(t_q, jnp.float32)
    if not use_kernel:
        return ref.fused_mf_sgd_ref(
            p_rows, q_rows, ratings, t_p, t_q, lr=lr, lam=lam,
            bias_u=bias_u, bias_i=bias_i, global_mean=global_mean,
            weight=weight,
        )
    if interpret is None:
        interpret = _default_interpret()
    b = p_rows.shape[0]
    has_bias = bias_u is not None

    def col(v, fill):
        full = jnp.full((b,), fill, jnp.float32) if v is None else v
        return _pad_to(full.astype(jnp.float32)[:, None], block_b, 0)

    pp = _pad_to(p_rows, block_b, 0)
    qp = _pad_to(q_rows, block_b, 0)
    rp = _pad_to(ratings[:, None].astype(jnp.float32), block_b, 0)
    mu = jnp.asarray(global_mean if has_bias else 0.0, jnp.float32)
    new_p, new_q, new_bu, new_bi, err = fused_mf_sgd_padded(
        pp,
        qp,
        rp,
        col(bias_u, 0.0),
        col(bias_i, 0.0),
        col(weight, 1.0),  # padding rows get weight 0 from _pad_to
        t_p.reshape(1, 1),
        t_q.reshape(1, 1),
        mu.reshape(1, 1),
        lr=lr,
        lam=lam,
        block_b=block_b,
        interpret=interpret,
    )
    return (
        new_p[:b],
        new_q[:b],
        new_bu[:b, 0] if has_bias else None,
        new_bi[:b, 0] if has_bias else None,
        err[:b, 0],
    )


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "k"))
def tile_block_stats(
    r_u: jax.Array,
    r_i: jax.Array,
    k: int,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
):
    """Instrumentation: fraction of K-blocks the kernel executes vs dense.

    Deterministic from the ranks (the kernel's ``pl.when`` bound), so it can
    be computed without instrumenting the kernel itself.  Also returns the
    element-exact work fraction (the paper's per-element early stop) to show
    how much the tile quantization gives back.
    """
    rup = _pad_to(r_u.astype(jnp.int32), block_m, 0)
    rip = _pad_to(r_i.astype(jnp.int32), block_n, 0)
    tu = jnp.max(rup.reshape(-1, block_m), axis=1)  # per-M-tile max rank
    ti = jnp.max(rip.reshape(-1, block_n), axis=1)  # per-N-tile max rank
    bound = jnp.minimum(tu[:, None], ti[None, :]).astype(jnp.float32)
    nk = -(-k // block_k)
    blocks = jnp.ceil(bound / block_k)
    tile_fraction = jnp.mean(blocks) / nk
    elem_fraction = jnp.mean(
        jnp.minimum(r_u[:, None], r_i[None, :]).astype(jnp.float32)
    ) / float(k)
    return tile_fraction, elem_fraction
