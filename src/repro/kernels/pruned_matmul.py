"""Tile-ragged-K pruned matmul — the TPU-native form of the paper's Alg. 2.

Computes ``out[u, i] = sum_{t < min(r_u[u], r_i[i])} p[u, t] * q[i, t]`` for
all pairs, where ``r_u``/``r_i`` are the per-row effective ranks of the
(rearranged) factor matrices.

TPU adaptation of the paper's scalar early-exit (see DESIGN.md §2):

* the (M, N, K) iteration space is tiled into MXU-aligned blocks held in VMEM
  via ``BlockSpec``;
* for each (M-tile, N-tile), whole K-blocks past the tile bound
  ``min(max_tile(r_u), max_tile(r_i))`` are skipped with ``pl.when`` — this is
  where the paper's "unnecessary computation" is actually not executed;
* partially-covered K-blocks are element-masked with ``broadcasted_iota`` so
  the result equals the reference oracle exactly (not approximately).

Because Alg. 1 sorts the latent axis by joint sparsity, rank values are
front-loaded and correlated, so the per-tile ``max`` stays close to individual
ranks and tile-level skipping recovers most of the element-level savings
(measured in benchmarks/bench_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(p_ref, q_ref, ru_ref, ri_ref, o_ref, acc_ref, *, block_k: int):
    """One (M-tile, N-tile, K-block) grid step."""
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Tile bound: the largest pair rank within this (M, N) tile.  Every
    # product term in K-blocks at or past the bound is zero by construction,
    # so the whole block is skipped — the TPU analogue of the paper's break.
    bound = jnp.minimum(jnp.max(ru_ref[...]), jnp.max(ri_ref[...]))

    @pl.when(ik * block_k < bound)
    def _compute():
        bm, bk = p_ref.shape
        bn = q_ref.shape[0]
        t0 = ik * block_k
        # Element masks: zero each operand's suffix (t >= own rank).  The
        # product mask is then t < min(r_u, r_i), matching Alg. 2 exactly.
        tp_idx = t0 + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
        tq_idx = t0 + jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1)
        pm = jnp.where(tp_idx < ru_ref[...], p_ref[...], 0.0).astype(jnp.float32)
        qm = jnp.where(tq_idx < ri_ref[...], q_ref[...], 0.0).astype(jnp.float32)
        # HIGHEST: float32-accurate like the pinned XLA oracle it must match
        acc_ref[...] += jax.lax.dot_general(
            pm,
            qm,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret"),
)
def pruned_matmul_padded(
    p: jax.Array,    # (M, K), M % block_m == 0, K % block_k == 0
    q: jax.Array,    # (N, K), N % block_n == 0
    r_u: jax.Array,  # (M, 1) int32
    r_i: jax.Array,  # (N, 1) int32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, k = p.shape
    n = q.shape[0]
    grid = (m // block_m, n // block_n, k // block_k)

    kernel = functools.partial(_kernel, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((block_n, block_k), lambda im, jn, ik: (jn, ik)),
            pl.BlockSpec((block_m, 1), lambda im, jn, ik: (im, 0)),
            pl.BlockSpec((block_n, 1), lambda im, jn, ik: (jn, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(p, q, r_u, r_i)
