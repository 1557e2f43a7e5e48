"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode executes the Pallas kernel bodies on CPU), and the training
step under plain SGD against its one-step oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.ranks import effective_ranks
from repro.kernels import pruned_matmul, ref, tile_block_stats

from sgd_step import train_step_on_rows, weighted_mean_abs


def _factors(m, n, k, dtype, seed=0, scale=0.1):
    rng = np.random.default_rng(seed)
    p = rng.normal(0, scale, (m, k)).astype(np.float32)
    q = rng.normal(0, scale, (n, k)).astype(np.float32)
    return jnp.asarray(p, dtype), jnp.asarray(q, dtype)


MATMUL_SHAPES = [
    # (m, n, k, bm, bn, bk) — aligned, ragged, tiny, tall/skinny
    (128, 128, 128, 64, 64, 32),
    (100, 77, 40, 32, 32, 16),
    (1, 300, 50, 8, 128, 64),
    (257, 63, 129, 128, 32, 128),
    (16, 16, 8, 16, 16, 8),
]


@pytest.mark.parametrize("m,n,k,bm,bn,bk", MATMUL_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t", [0.0, 0.06, 0.5])
def test_pruned_matmul_vs_ref(m, n, k, bm, bn, bk, dtype, t):
    p, q = _factors(m, n, k, dtype)
    r_u = effective_ranks(p, t)
    r_i = effective_ranks(q, t)
    expected = ref.pruned_matmul_ref(p, q, r_u, r_i)
    got = pruned_matmul(p, q, t, t, block_m=bm, block_n=bn, block_k=bk)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,k", [(64, 32), (33, 50), (7, 16), (256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t", [0.0, 0.06])
def test_sgd_step_vs_ref(b, k, dtype, t):
    """The training step under plain SGD (Algs. 2 + 3 on the gathered rows)
    matches the one-step oracle, rows and error."""
    rng = np.random.default_rng(1)
    p = jnp.asarray(rng.normal(0, 0.1, (b, k)).astype(np.float32), dtype)
    q = jnp.asarray(rng.normal(0, 0.1, (b, k)).astype(np.float32), dtype)
    r = jnp.asarray(rng.uniform(1, 5, (b,)).astype(np.float32))
    exp_p, exp_q, _, _, exp_e = ref.sgd_step_ref(
        p, q, r, jnp.float32(t), jnp.float32(t), lr=0.05, lam=0.02
    )
    got_p, got_q, got_bu, got_bi, got_err = train_step_on_rows(
        p, q, r, t, lr=0.05, lam=0.02
    )
    assert got_bu is None and got_bi is None  # unbiased model
    assert got_p.dtype == dtype and got_q.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got_p, np.float32),
                               np.asarray(exp_p, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got_q, np.float32),
                               np.asarray(exp_q, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(float(got_err), float(weighted_mean_abs(exp_e)),
                               rtol=tol, atol=tol)


def test_kernel_under_jit_grad_free():
    """Wrappers compose with jit (dry-run-style lowering works)."""
    p, q = _factors(64, 64, 32, jnp.float32)

    @jax.jit
    def f(p, q):
        return pruned_matmul(p, q, 0.06, 0.06, block_m=32, block_n=32, block_k=16)

    out = f(p, q)
    assert out.shape == (64, 64)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_tile_stats_bounds_and_sorted_gain():
    """Tile-level skip fraction is an upper bound on element work; sorting
    the latent axis (rearrangement) tightens per-tile bounds vs a random
    permutation — the mechanism DESIGN.md §2 relies on."""
    rng = np.random.default_rng(2)
    k = 128
    # rank-correlated matrices: significance concentrated at low t (post-Alg.1)
    decay = np.exp(-np.arange(k) / 20.0)
    p = (rng.normal(0, 0.1, (512, k)) * decay).astype(np.float32)
    q = (rng.normal(0, 0.1, (512, k)) * decay).astype(np.float32)
    t = 0.05
    r_u = effective_ranks(jnp.asarray(p), t)
    r_i = effective_ranks(jnp.asarray(q), t)
    tile_sorted, elem = tile_block_stats(r_u, r_i, k, block_m=64, block_n=64, block_k=16)
    assert float(tile_sorted) >= float(elem) - 1e-6

    perm = rng.permutation(k)
    r_u_s = effective_ranks(jnp.asarray(p[:, perm]), t)
    r_i_s = effective_ranks(jnp.asarray(q[:, perm]), t)
    tile_shuffled, _ = tile_block_stats(r_u_s, r_i_s, k, block_m=64, block_n=64, block_k=16)
    assert float(tile_sorted) <= float(tile_shuffled) + 1e-6


def test_pruned_matmul_skips_match_prediction():
    """The kernel's computed output must be identical whether a K-block is
    skipped (bound) or computed-then-masked — checked by comparing against
    a run with pruning disabled but inputs pre-masked."""
    p, q = _factors(128, 128, 64, jnp.float32, seed=3)
    t = 0.08
    r_u = effective_ranks(p, t)
    r_i = effective_ranks(q, t)
    from repro.core.ranks import rank_mask

    p_masked = p * rank_mask(r_u, 64)
    q_masked = q * rank_mask(r_i, 64)
    dense_of_masked = pruned_matmul(
        p_masked, q_masked, 0.0, 0.0, block_m=32, block_n=32, block_k=16
    )
    pruned = pruned_matmul(p, q, t, t, block_m=32, block_n=32, block_k=16)
    np.testing.assert_allclose(
        np.asarray(pruned), np.asarray(dense_of_masked), rtol=1e-5, atol=1e-6
    )
