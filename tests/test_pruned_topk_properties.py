"""Property tests: `kernels/pruned_topk.py` vs the dense argsort oracle.

Factors (and biases) are drawn on the 1/8 grid, so every pruned dot product
is a multiple of 1/64 well inside f32's exact-integer range: all scoring
paths compute the *exact* mathematical score regardless of tile shape or
summation order.  That makes two strong assertions safe:

* scores match the oracle **bitwise**, not just within a tolerance;
* score ties (e.g. duplicated item rows) are mathematically exact, so index
  parity genuinely pins the tie-breaking contract (lower item index wins,
  the stable-argsort order) across the streaming scan, the Pallas kernel's
  max-extraction merge, and the oracle.

Hypothesis drives the shape/threshold/duplication space (skipped gracefully
when hypothesis is absent — see ``hypothesis_compat``); the parametrized
edge cases below run everywhere and share the same checker, covering the
corners the issue names: ragged ranks, duplicate scores, ``topk == n``, and
tiny/odd tile shapes.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from hypothesis_compat import given, settings, st
from repro.core.ranks import effective_ranks
from repro.kernels import ops, ref


def _grid(rng, shape):
    """f32 values on the 1/8 grid in [-2, 2] — exactly representable."""
    return (rng.integers(-16, 17, shape) / 8.0).astype(np.float32)


def _duplicate_rows(rng, q):
    """Copy random rows over random rows: exact score ties across items."""
    n = q.shape[0]
    count = max(1, n // 2)
    q = q.copy()
    q[rng.integers(0, n, count)] = q[rng.integers(0, n, count)]
    return q


def _check_case(p, q, t_p, t_q, topk, bias, *, use_kernel, **blocks):
    p, q = jnp.asarray(p), jnp.asarray(q)
    b = None if bias is None else jnp.asarray(bias)
    r_u, r_i = effective_ranks(p, t_p), effective_ranks(q, t_q)
    want_s, want_i = ref.pruned_topk_ref(p, q, r_u, r_i, topk, item_bias=b)
    got_s, got_i = ops.pruned_topk(
        p, q, t_p, t_q, topk,
        item_bias=b, use_kernel=use_kernel, interpret=True, **blocks,
    )
    assert np.array_equal(np.asarray(want_i), np.asarray(got_i)), (
        "indices diverged from the dense argsort oracle"
    )
    assert np.array_equal(np.asarray(want_s), np.asarray(got_s)), (
        "scores diverged (grid inputs make exact equality the contract)"
    )


# ---------------------------------------------------------------------------
# hypothesis: the shape / threshold / tie space
# ---------------------------------------------------------------------------

_THRESHOLDS = [0.0, 1 / 16, 1 / 8, 3 / 8]  # 0 disables pruning; 3/8 is harsh


@st.composite
def topk_cases(draw):
    m = draw(st.integers(1, 20))
    n = draw(st.integers(1, 80))
    k = draw(st.integers(1, 24))
    topk = draw(st.integers(1, n))
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    p = _grid(rng, (m, k))
    q = _grid(rng, (n, k))
    if draw(st.booleans()) and n >= 2:
        q = _duplicate_rows(rng, q)
    t_p = draw(st.sampled_from(_THRESHOLDS))
    t_q = draw(st.sampled_from(_THRESHOLDS))
    bias = _grid(rng, (n,)) if draw(st.booleans()) else None
    return p, q, t_p, t_q, topk, bias


@given(topk_cases(), st.sampled_from([1, 3, 7, 16, 128]))
@settings(max_examples=30, deadline=None)
def test_streaming_topk_property(case, block_n):
    """Ragged ranks, duplicate scores, k >= n, odd streaming tile widths."""
    p, q, t_p, t_q, topk, bias = case
    _check_case(p, q, t_p, t_q, topk, bias, use_kernel=False, block_n=block_n)


@given(topk_cases())
@settings(max_examples=10, deadline=None)
def test_pallas_kernel_topk_property(case):
    """Same space through the Pallas kernel (interpret mode) at small/odd
    block shapes, so tile padding, K-block skipping, and the in-kernel
    max-extraction merge all see ragged boundaries."""
    p, q, t_p, t_q, topk, bias = case
    _check_case(
        p, q, t_p, t_q, topk, bias,
        use_kernel=True, block_m=8, block_n=16, block_k=8,
    )


# ---------------------------------------------------------------------------
# deterministic edge cases (run with or without hypothesis)
# ---------------------------------------------------------------------------

_EDGE_CASES = [
    # (m, n, k, topk, t, dup, bias) — named by what they corner
    pytest.param(1, 1, 1, 1, 0.0, False, False, id="degenerate-1x1x1"),
    pytest.param(5, 9, 3, 9, 1 / 16, False, True, id="topk-equals-n"),
    pytest.param(8, 33, 7, 5, 1 / 8, True, True, id="dup-ties-odd-shapes"),
    pytest.param(16, 130, 24, 17, 3 / 8, True, False, id="harsh-ragged-ranks"),
    pytest.param(3, 12, 4, 12, 10.0, False, True, id="all-ranks-zero"),
]


@pytest.mark.parametrize("use_kernel", [False, True], ids=["stream", "kernel"])
@pytest.mark.parametrize("m,n,k,topk,t,dup,bias", _EDGE_CASES)
def test_topk_edge_cases(m, n, k, topk, t, dup, bias, use_kernel):
    rng = np.random.default_rng(m * 1000 + n)
    p = _grid(rng, (m, k))
    q = _grid(rng, (n, k))
    if dup and n >= 2:
        q = _duplicate_rows(rng, q)
    b = _grid(rng, (n,)) if bias else None
    blocks = (
        dict(block_m=8, block_n=16, block_k=8) if use_kernel
        else dict(block_n=7)
    )
    _check_case(p, q, t, t, topk, b, use_kernel=use_kernel, **blocks)


def test_topk_out_of_range_raises():
    """k > n is a request error, not a deep lax.top_k trace failure."""
    rng = np.random.default_rng(0)
    p, q = _grid(rng, (4, 8)), _grid(rng, (16, 8))
    for use_kernel in (False, True):
        with pytest.raises(ValueError, match="topk"):
            ops.pruned_topk(p, q, 0.0, 0.0, 17, use_kernel=use_kernel)
        with pytest.raises(ValueError, match="topk"):
            ops.pruned_topk(p, q, 0.0, 0.0, 0, use_kernel=use_kernel)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["stream", "kernel"])
@pytest.mark.parametrize("t", [0.0, 1 / 16, 1 / 8])
def test_sasrec_session_vectors_topk_parity(t, use_kernel):
    """Session-shaped factor pairs through both top-k paths.

    Real SASRec final-state encodings (``workloads.sequential``) scored
    against the item embedding table — snapped to the 1/8 grid so the
    file's bitwise-equality contract holds through the kernel's split-k
    reduction.  This is the serving geometry the sequential workload
    produces: p rows are transformer outputs (dense, unnormalized), q is an
    embedding table with its padding row dropped, no biases, topk == n.
    """
    from repro.data import clicks
    from repro.models import recsys
    from repro.workloads import sequential

    cfg = recsys.SASRecConfig(
        n_items=33, embed_dim=16, n_blocks=2, n_heads=2, seq_len=8
    )
    import jax

    sasrec = recsys.init_sasrec_params(jax.random.PRNGKey(4), cfg)
    seqs = clicks.sasrec_batch(9, seq_len=8, n_items=33, seed=4)["seq"]
    view = sequential.session_params(sasrec, jnp.asarray(seqs), cfg)
    # snap to the grid; rescale first so the thresholds bite mid-row
    snap = lambda a: np.round(np.asarray(a) * 8.0).astype(np.float32) / 8.0
    p = snap(view.p)
    q = snap(view.q * 40.0)   # embed init is ~0.01-scale: lift onto the grid
    assert (np.abs(q) > 0).any()
    blocks = (
        dict(block_m=8, block_n=16, block_k=8) if use_kernel
        else dict(block_n=7)
    )
    _check_case(p, q, t, t, q.shape[0], None, use_kernel=use_kernel, **blocks)


def test_total_pruning_serves_bias_order():
    """Thresholds above every |factor|: all ranks 0, every dot product empty
    — the top-k must then be exactly the bias ordering (maximal tie stress
    everywhere bias repeats)."""
    rng = np.random.default_rng(7)
    p, q = _grid(rng, (6, 5)), _grid(rng, (40, 5))
    bias = _grid(rng, (40,))
    s, i = ops.pruned_topk(
        p, q, 10.0, 10.0, 40, item_bias=jnp.asarray(bias), use_kernel=False
    )
    order = np.argsort(-bias, kind="stable").astype(np.int32)
    assert np.array_equal(np.asarray(i), np.tile(order, (6, 1)))
    assert np.array_equal(np.asarray(s), np.tile(bias[order], (6, 1)))


# ---------------------------------------------------------------------------
# the kernel's adaptive merge: catalogs that set its pass count, and the
# counter of those passes
# ---------------------------------------------------------------------------

_MERGE_BLOCKS = dict(block_m=8, block_n=16, block_k=8)
_MERGE_N = 170   # not a multiple of block_n; >= every topk below


def _merge_catalog(kind, topk, n=_MERGE_N):
    """``(p, q, t_p, bias)`` of one merge-stress catalog.  Users score every
    catalog the same way up to a positive scale, except ``ragged``."""
    rng = np.random.default_rng(topk)
    p = rng.integers(1, 9, (6, 1)).astype(np.float32) / 8
    t_p, bias = 0.0, None
    if kind == "ascending":        # every tile beats the running top-k
        q = np.arange(1, n + 1, dtype=np.float32)[:, None] / 8
    elif kind == "descending":     # only the first tiles enter
        q = np.arange(n, 0, -1, dtype=np.float32)[:, None] / 8
    elif kind == "all_equal":      # rank-0 users, no bias: every score is 0
        p, q, t_p = _grid(rng, (6, 8)), _grid(rng, (n, 8)), 10.0
    elif kind == "kth_tie":
        # The first topk items hold topk/8 ... 1/8, so the running k-th best
        # is 1/8; later items tie it (must not enter) or repeat 2/8, whose
        # first copy enters and lifts the k-th best to itself, so the next
        # copy ties it in turn.
        later = np.arange(n - topk)
        dup = 2.0 if topk >= 2 else 1.0
        tail = np.where(later % 7 == 3, dup, np.where(later % 3 == 0, 1.0, 0.0))
        q = np.concatenate([np.arange(topk, 0, -1), tail])
        q = (q / 8).astype(np.float32)[:, None]
    else:                          # random ties and ranks, with a bias
        p, q = _grid(rng, (6, 8)), _duplicate_rows(rng, _grid(rng, (n, 8)))
        t_p, bias = 1 / 16, _grid(rng, (n,))
    return p, q, t_p, bias


@pytest.mark.parametrize("topk", [1, 10, 100, 128])
@pytest.mark.parametrize(
    "kind", ["ascending", "descending", "all_equal", "kth_tie", "ragged"]
)
def test_adaptive_merge_bitwise(kind, topk):
    """The merge runs only the passes a tile needs, yet scores and indices
    stay bitwise those of the stable dense argsort: strict ``>`` against
    the k-th best, insertion behind running entries ``>=`` the score, and
    padded item rows never entering."""
    p, q, t_p, bias = _merge_catalog(kind, topk)
    _check_case(p, q, t_p, 0.0, topk, bias, use_kernel=True, **_MERGE_BLOCKS)
    if kind == "all_equal":
        _, got_i = ops.pruned_topk(
            p, q, t_p, 0.0, topk, interpret=True, **_MERGE_BLOCKS
        )
        assert np.array_equal(
            np.asarray(got_i), np.tile(np.arange(topk), (p.shape[0], 1))
        )


def _padded_scores(p_rows, q, bias, block_m, block_n):
    """Dense scores the kernel sees at thresholds 0: user rows padded with
    zero rows to ``block_m``, item columns past ``n`` at -inf."""
    m, n = p_rows.shape[0], q.shape[0]
    rows = np.zeros((-(-m // block_m) * block_m, q.shape[1]))
    rows[:m] = p_rows
    scores = np.full((rows.shape[0], -(-n // block_n) * block_n), -np.inf)
    scores[:, :n] = rows @ q.astype(np.float64).T
    if bias is not None:
        scores[:, :n] += bias
    return scores


def _recount_passes(scores, block_m, block_n, topk):
    """Per user block, the merge passes summed over its item tiles: each
    tile's largest count over the block's rows of scores strictly above the
    row's running k-th best, capped at ``topk``."""
    out = []
    for lo in range(0, scores.shape[0], block_m):
        rows = scores[lo : lo + block_m]
        run = np.full((rows.shape[0], topk), -np.inf)
        passes = 0
        for j in range(0, rows.shape[1], block_n):
            tile = rows[:, j : j + block_n]
            above = (tile > run[:, -1:]).sum(axis=1)
            passes += min(int(above.max()), topk)
            run = -np.sort(-np.concatenate([run, tile], axis=1), axis=1)[:, :topk]
        out.append(passes)
    return out


@pytest.mark.parametrize("kind", ["ascending", "descending", "random"])
def test_merge_pass_counter_matches_recount(kind):
    """``passes`` equals a numpy recount; an ascending catalog takes topk
    passes every tile, a descending one topk in tile 0 and none after.
    Columns past topk stay -inf / 0 filler."""
    from repro.kernels.pruned_topk import pruned_topk_padded

    block_m, block_n, topk, m, n = 8, 16, 10, 13, 96
    rng = np.random.default_rng(5)
    if kind == "random":
        p, q, bias = _grid(rng, (m, 8)), _grid(rng, (n, 8)), _grid(rng, (n,))
    else:
        p = rng.integers(1, 9, (m, 1)).astype(np.float32) / 8
        order = np.arange(1, n + 1) if kind == "ascending" else np.arange(n, 0, -1)
        q, bias = (order[:, None] / 8).astype(np.float32), None
    pj, qj = jnp.asarray(p), jnp.asarray(q)
    pp, rup = ops.pad_users_for_topk_kernel(
        pj, effective_ranks(pj, 0.0), block_m=block_m, block_k=8
    )
    qp, rip, biasp = ops.pad_catalog_for_topk_kernel(
        qj, effective_ranks(qj, 0.0), None if bias is None else jnp.asarray(bias),
        block_n=block_n, block_k=8,
    )
    scores, idx, passes = pruned_topk_padded(
        pp, qp, rup, rip, biasp, topk=topk, n_items=n,
        block_m=block_m, block_n=block_n, block_k=8, interpret=True,
    )
    # columns past topk stay filler
    assert (np.asarray(scores)[:, topk:] == -np.inf).all()
    assert (np.asarray(idx)[:, topk:] == 0).all()
    passes = np.asarray(passes)
    assert passes.shape == (2, 1, 128)
    assert (passes == passes[:, :, :1]).all()
    got = passes[:, 0, 0].tolist()
    assert got == _recount_passes(
        _padded_scores(p, q, bias, block_m, block_n), block_m, block_n, topk
    )
    if kind == "ascending":
        assert got == [topk * (n // block_n)] * 2
    elif kind == "descending":
        assert got == [topk] * 2


def test_engine_merge_counters_accumulate():
    """``ServingEngine.merge_passes`` / ``merge_tiles`` add up every kernel
    launch of every ``topk`` call: chunks of ``max_batch`` users, each
    padded to its bucket by repeating its last user, then to the kernel's
    user block by zero rows."""
    import jax

    from repro.core import mf
    from repro.kernels.pruned_topk import TOPK_BLOCK_M, TOPK_BLOCK_N
    from repro.serving import ServingEngine, bucket_size

    rng = np.random.default_rng(11)
    m, n, k, max_batch = 20, 300, 8, 8
    p, q = _grid(rng, (m, k)), _grid(rng, (n, k))
    params = mf.init_params(jax.random.PRNGKey(0), m, n, k)._replace(
        p=jnp.asarray(p), q=jnp.asarray(q)
    )
    engine = ServingEngine(
        params, 0.0, 0.0, use_kernel=True, interpret=True, max_batch=max_batch
    )
    assert engine.merge_passes == engine.merge_tiles == 0
    want_passes = want_tiles = 0
    for users, topk in ((np.arange(5), 7), (np.arange(5, 18), 12)):
        engine.topk(users, topk)
        for lo in range(0, len(users), max_batch):
            chunk = users[lo : lo + max_batch]
            pad = bucket_size(len(chunk), max_batch) - len(chunk)
            rows = p[np.pad(chunk, (0, pad), mode="edge")]
            blocks = _recount_passes(
                _padded_scores(rows, q, None, TOPK_BLOCK_M, TOPK_BLOCK_N),
                TOPK_BLOCK_M, TOPK_BLOCK_N, topk,
            )
            want_passes += sum(blocks)
            want_tiles += len(blocks) * -(-n // TOPK_BLOCK_N)
        assert (engine.merge_passes, engine.merge_tiles) == (want_passes, want_tiles)
    assert want_tiles == 3 * 2
