"""Fused pruned-score + tiled top-k — the serving-time form of Alg. 2.

Computes, for every user row, the top-k items of
``score[u, i] = sum_{t < min(r_u[u], r_i[i])} p[u, t] * q[i, t] + bias[i]``
WITHOUT ever materializing the (M, N) score matrix in HBM.  At catalog scale
the dense serve path is memory-bound on exactly that matrix (score + argsort
over N items per user); here each (M-tile, N-tile) block of scores lives only
in a VMEM accumulator and is folded into a running per-user top-k before the
next item tile is scored.

Structure (reuses the ragged-K tile skipping of ``pruned_matmul.py``):

* grid (M-tiles, N-tiles, K-blocks); the N/K axes are sequential
  ("arbitrary") because the running top-k scratch carries state across item
  tiles, M-tiles are parallel;
* whole K-blocks past the tile bound ``min(max(r_u), max(r_i))`` are skipped
  with ``pl.when`` — the paper's "unnecessary computation" not executed;
* partially-covered K-blocks are element-masked with ``broadcasted_iota`` so
  scores are exactly the oracle's;
* on the last K-block the (bm, bn) score tile is merged into the running
  (bm, topk) scores/indices scratch by iterative max-extraction (k vector
  passes — no sort network needed on the VPU; ties resolve to the lower item
  index, matching a stable dense argsort);
* the merged result is written to the output only on the final item tile.

Peak HBM for serving B users is therefore O(B * topk) instead of O(B * N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")

# Default block geometry.  Every producer of padded operands (kernels.ops
# wrappers, the serving engine's precomputed catalog layouts) imports these,
# so retuning the kernel retunes the whole layout contract at once.
TOPK_BLOCK_M = 128
TOPK_BLOCK_N = 256
TOPK_BLOCK_K = 128


def _merge_topk(run_s, run_i, tile_s, tile_i, topk: int):
    """Merge a (bm, bn) score tile into the (bm, P) running top-k buffers.

    Iterative max-extraction: ``topk`` passes of rowwise max + first-match
    select over the concatenated candidates.  First-match (minimum position)
    prefers the running buffer, i.e. earlier = lower item indices, which is
    exactly the tie order of a stable dense argsort.
    """
    bm = run_s.shape[0]
    cand_s = jnp.concatenate([run_s, tile_s], axis=1)
    cand_i = jnp.concatenate([run_i, tile_i], axis=1)
    width = cand_s.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (bm, width), 1)

    out_s, out_i = [], []
    for _ in range(topk):
        best = jnp.max(cand_s, axis=1, keepdims=True)
        sel = jnp.min(
            jnp.where((cand_s == best) & (best > _NEG_INF), pos, width),
            axis=1,
            keepdims=True,
        )
        hit = pos == sel  # one-hot row mask; all-False once a row runs dry
        out_s.append(jnp.max(jnp.where(hit, cand_s, _NEG_INF), axis=1, keepdims=True))
        out_i.append(jnp.max(jnp.where(hit, cand_i, 0), axis=1, keepdims=True))
        cand_s = jnp.where(hit, _NEG_INF, cand_s)

    pad = run_s.shape[1] - topk
    if pad:
        out_s.append(jnp.full((bm, pad), _NEG_INF, run_s.dtype))
        out_i.append(jnp.zeros((bm, pad), run_i.dtype))
    return jnp.concatenate(out_s, axis=1), jnp.concatenate(out_i, axis=1)


def _kernel(
    p_ref, q_ref, ru_ref, ri_ref, bias_ref, os_ref, oi_ref,
    acc_ref, ts_ref, ti_ref,
    *, block_k: int, topk: int, n_items: int,
):
    jn, ik = pl.program_id(1), pl.program_id(2)
    nj, nk = pl.num_programs(1), pl.num_programs(2)

    @pl.when((jn == 0) & (ik == 0))
    def _init_topk():
        ts_ref[...] = jnp.full_like(ts_ref, _NEG_INF)
        ti_ref[...] = jnp.zeros_like(ti_ref)

    @pl.when(ik == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Ragged-K tile skipping, identical to pruned_matmul: every product term
    # in K-blocks at or past the tile's pair-rank bound is zero.
    bound = jnp.minimum(jnp.max(ru_ref[...]), jnp.max(ri_ref[...]))

    @pl.when(ik * block_k < bound)
    def _compute():
        bm, bk = p_ref.shape
        bn = q_ref.shape[0]
        t0 = ik * block_k
        tp_idx = t0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
        tq_idx = t0 + jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1)
        pm = jnp.where(tp_idx < ru_ref[...], p_ref[...], 0.0).astype(jnp.float32)
        qm = jnp.where(tq_idx < ri_ref[...], q_ref[...], 0.0).astype(jnp.float32)
        # HIGHEST: float32-accurate like the pinned XLA oracle it must match
        acc_ref[...] += jax.lax.dot_general(
            pm, qm,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _merge():
        bm, bn = acc_ref.shape
        col = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        gidx = jn * bn + col
        scores = acc_ref[...] + bias_ref[...].reshape(1, bn)
        # padded catalog rows (q rows past n_items) must never be selected
        scores = jnp.where(gidx < n_items, scores, _NEG_INF)
        new_s, new_i = _merge_topk(ts_ref[...], ti_ref[...], scores, gidx, topk)
        ts_ref[...] = new_s
        ti_ref[...] = new_i

    @pl.when((jn == nj - 1) & (ik == nk - 1))
    def _store():
        os_ref[...] = ts_ref[...]
        oi_ref[...] = ti_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "topk", "n_items", "block_m", "block_n", "block_k", "interpret"
    ),
)
def pruned_topk_padded(
    p: jax.Array,     # (M, K), M % block_m == 0, K % block_k == 0
    q: jax.Array,     # (N, K), N % block_n == 0 (rows >= n_items are padding)
    r_u: jax.Array,   # (M, 1) int32
    r_i: jax.Array,   # (N, 1) int32
    bias: jax.Array,  # (N, 1) float32 per-item additive bias (zeros if none)
    *,
    topk: int,
    n_items: int,
    block_m: int = TOPK_BLOCK_M,
    block_n: int = TOPK_BLOCK_N,
    block_k: int = TOPK_BLOCK_K,
    interpret: bool = False,
):
    """Padded-shape kernel entry.  Returns ``(scores, indices)`` shaped
    (M, topk_pad) with ``topk_pad = topk`` rounded up to the 128-lane tile;
    columns past ``topk`` are -inf / 0 filler."""
    m, k = p.shape
    n = q.shape[0]
    topk_pad = -(-topk // 128) * 128
    grid = (m // block_m, n // block_n, k // block_k)

    kernel = functools.partial(
        _kernel, block_k=block_k, topk=topk, n_items=n_items
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((block_n, block_k), lambda im, jn, ik: (jn, ik)),
            pl.BlockSpec((block_m, 1), lambda im, jn, ik: (im, 0)),
            pl.BlockSpec((block_n, 1), lambda im, jn, ik: (jn, 0)),
            pl.BlockSpec((block_n, 1), lambda im, jn, ik: (jn, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, topk_pad), lambda im, jn, ik: (im, 0)),
            pl.BlockSpec((block_m, topk_pad), lambda im, jn, ik: (im, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, topk_pad), jnp.float32),
            jax.ShapeDtypeStruct((m, topk_pad), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, topk_pad), jnp.float32),
            pltpu.VMEM((block_m, topk_pad), jnp.int32),
        ],
        # the N (item-tile) axis carries the running top-k scratch, so only
        # the user-tile axis is parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(p, q, r_u, r_i, bias)
