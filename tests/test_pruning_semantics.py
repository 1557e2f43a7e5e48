"""Property tests pinning the vectorized pruning machinery to the paper's
scalar algorithms (Algs. 1-3, Eqs. 7-8)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis_compat import given, settings, st

from repro.core import ranks, rearrange, threshold
from repro.kernels import ref


def factor_matrices(draw, max_rows=24, max_k=16):
    m = draw(st.integers(1, max_rows))
    n = draw(st.integers(1, max_rows))
    k = draw(st.integers(1, max_k))
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    p = rng.normal(0, 0.1, (m, k)).astype(np.float32)
    q = rng.normal(0, 0.1, (n, k)).astype(np.float32)
    return p, q


@st.composite
def pq_strategy(draw):
    return factor_matrices(draw)


@given(pq_strategy(), st.floats(0.0, 0.25), st.floats(0.0, 0.25))
@settings(max_examples=40, deadline=None)
def test_masked_dot_equals_paper_loop(pq, t_p, t_q):
    """ranks.pruned_pair_dot == Algorithm 2's early-stopped scalar loop."""
    p, q = pq
    m, k = p.shape
    n = q.shape[0]
    r_u = ranks.effective_ranks(jnp.asarray(p), t_p)
    r_i = ranks.effective_ranks(jnp.asarray(q), t_q)
    out = ref.pruned_matmul_ref(jnp.asarray(p), jnp.asarray(q), r_u, r_i)
    for u in range(min(m, 4)):
        for i in range(min(n, 4)):
            expected = ref.early_stop_dot_loop(p[u], q[i], t_p, t_q)
            np.testing.assert_allclose(float(out[u, i]), expected, atol=1e-5)


@given(pq_strategy(), st.floats(0.01, 0.2))
@settings(max_examples=30, deadline=None)
def test_rearrangement_preserves_predictions(pq, t):
    """Permuting the shared latent axis never changes ANY unpruned inner
    product (the rearrangement is error-free by itself)."""
    p, q = pq
    res = rearrange.rearrangement(jnp.asarray(p), jnp.asarray(q), t, t)
    p2, q2 = rearrange.apply_perm(jnp.asarray(p), jnp.asarray(q), res.perm)
    np.testing.assert_allclose(
        np.asarray(p2 @ q2.T), p @ q.T, rtol=1e-5, atol=1e-6
    )
    # joint sparsity is ascending after rearrangement (paper Eq. 11)
    js = np.asarray(res.joint_sparsity)
    assert np.all(np.diff(js) >= -1e-7)


@given(pq_strategy())
@settings(max_examples=30, deadline=None)
def test_zero_threshold_is_dense(pq):
    """Thresholds 0 must recover the dense computation exactly (the paper's
    rate-0 baseline shares the code path)."""
    p, q = pq
    r_u = ranks.effective_ranks(jnp.asarray(p), 0.0)
    assert int(jnp.min(r_u)) == p.shape[1]
    out = ref.pruned_matmul_ref(
        jnp.asarray(p), jnp.asarray(q), r_u, ranks.effective_ranks(jnp.asarray(q), 0.0)
    )
    np.testing.assert_allclose(np.asarray(out), p @ q.T, rtol=1e-5, atol=1e-6)


@given(
    st.floats(-0.05, 0.05),   # mu
    st.floats(0.02, 0.5),     # sigma
    st.floats(0.01, 0.95),    # rate
)
@settings(max_examples=50, deadline=None)
def test_threshold_solves_eq8(mu, sigma, rate):
    """T from Eqs. 7/8 prunes exactly `rate` mass of N(mu, sigma^2)."""
    from jax.scipy.stats import norm

    t = threshold.threshold_for_rate(
        threshold.MatrixStats(jnp.float32(mu), jnp.float32(sigma)), rate
    )
    t = float(t)
    mass = float(norm.cdf((t - mu) / sigma) - norm.cdf((-t - mu) / sigma))
    assert abs(mass - rate) < 1e-3


@given(st.floats(0.02, 0.5), st.lists(st.floats(0.05, 0.9), min_size=2, max_size=5))
@settings(max_examples=25, deadline=None)
def test_threshold_monotone_in_rate(sigma, rates):
    stats = threshold.MatrixStats(jnp.float32(0.0), jnp.float32(sigma))
    ts = [float(threshold.threshold_for_rate(stats, r)) for r in sorted(rates)]
    assert all(b >= a - 1e-7 for a, b in zip(ts, ts[1:]))


def test_threshold_matches_empirical_fraction():
    """End-to-end: measured matrices + Eq. 7/8 -> empirical pruned fraction
    close to the requested rate (the paper's §4.2 claim)."""
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.normal(0.01, 0.09, (4000, 40)).astype(np.float32))
    for rate in (0.1, 0.3, 0.5):
        t = threshold.threshold_for_rate(threshold.measure_stats(m), rate)
        frac = float(threshold.empirical_pruned_fraction(m, t))
        assert abs(frac - rate) < 0.02, (rate, frac)


@given(pq_strategy(), st.floats(0.01, 0.2), st.floats(0.01, 0.1), st.floats(0.0, 0.1))
@settings(max_examples=25, deadline=None)
def test_fused_sgd_matches_paper_update_loop(pq, t, lr, lam):
    """The one-step SGD oracle == Algorithm 3's truncated scalar update,
    pair by pair."""
    p, q = pq
    n_pairs = min(p.shape[0], q.shape[0], 5)
    p_rows = p[:n_pairs]
    q_rows = q[:n_pairs]
    ratings = np.linspace(1, 5, n_pairs).astype(np.float32)
    new_p, new_q, _, _, err = ref.sgd_step_ref(
        jnp.asarray(p_rows), jnp.asarray(q_rows), jnp.asarray(ratings),
        jnp.float32(t), jnp.float32(t), lr=lr, lam=lam,
    )
    for b in range(n_pairs):
        exp_p, exp_q, exp_err = ref.early_stop_update_loop(
            p_rows[b], q_rows[b], float(ratings[b]), t, t, lr, lam
        )
        np.testing.assert_allclose(np.asarray(new_p[b]), exp_p, atol=1e-5)
        np.testing.assert_allclose(np.asarray(new_q[b]), exp_q, atol=1e-5)
        np.testing.assert_allclose(float(err[b]), exp_err, atol=1e-5)


@given(pq_strategy(), st.floats(0.0, 0.3))
@settings(max_examples=25, deadline=None)
def test_work_fraction_bounds(pq, t):
    p, q = pq
    r_u = ranks.effective_ranks(jnp.asarray(p), t)
    r_i = ranks.effective_ranks(jnp.asarray(q), t)
    frac = float(
        ranks.work_fraction(r_u[:, None], r_i[None, :], p.shape[1])
    )
    assert 0.0 <= frac <= 1.0 + 1e-6


def test_rank_mask_matches_mask_rows():
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.normal(0, 0.1, (32, 16)).astype(np.float32))
    t = 0.06
    masked = ranks.mask_rows(rows, t)
    r = ranks.effective_ranks(rows, t)
    for i in range(32):
        ri = int(r[i])
        assert bool(jnp.all(masked[i, ri:] == 0))
        np.testing.assert_array_equal(
            np.asarray(masked[i, :ri]), np.asarray(rows[i, :ri])
        )
