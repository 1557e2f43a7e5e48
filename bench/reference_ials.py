"""Plain float32 reference of implicit ALS with dynamic pruning, independent
of the code under test.

Implicit ALS (Y. Hu, Y. Koren and C. Volinsky, "Collaborative Filtering for
Implicit Feedback Datasets", ICDM 2008) minimises, over every user x item
pair (Eq. 3),

    sum_ui c_ui (p_ui - x_u . y_i)^2 + lam (sum_u n_u ||x_u||^2 + sum_i n_i ||y_i||^2)

with p_ui = 1 where u interacted with i and 0 elsewhere, c_ui = 1 + alpha
r_ui (1 elsewhere), by alternating half-steps (section 4): with Y held,
each user solves

    x_u = (Y'Y + Y'(C_u - I)Y + lam n_u I)^-1 Y'C_u p(u)

and then each item likewise with X held.  Like ``bench/reference.py``,
whose rank, threshold, initialisation and permutation helpers it uses, it
imports nothing of the program and takes nothing the program made, and
follows in straightforward numpy and ``jax.numpy`` (float32, matrix
products at ``highest``):

* pairs: the log's (user, item) keys merged by ``np.unique``, a pair's r
  the sum of its ratings;
* a half-step: the fixed rows masked to their ranks (y~), G = Y~'Y~; each
  solved row's pairs cut into pieces of ``WIDTH`` (the side's longest row
  where that is shorter), the pieces' sums of (c - 1) y~ y~' and c y~ by a
  padded einsum, summed per row over its pieces (a hot row has many);
  A = G + that + lam n_u I and b = that sum of c y~; A's rows and columns
  from the row's rank r on replaced by the identity's and b[r:] by the
  old x[r:]; ``jnp.linalg.solve``; a row without pairs keeps its factors;
* an epoch: users, then items; after the first (dense) epoch the
  thresholds of Eq. 7/8 and Algorithm 1's permutation;
* the objective above with x~, y~ in the prediction: the observed pairs'
  c (1 - s)^2 - s^2 plus <X~'X~, Y~'Y~> for s^2 over all pairs (exact for
  s = x~ . y~), plus the penalty on the whole rows.

Departures from Hu et al. and Spark MLlib, each also the program's:

* lam is scaled by the row's count of pairs, as Spark's
  ``ALS(implicitPrefs=True)`` scales ``regParam`` (Zhou et al. 2008);
  Hu et al.'s lam is one constant;
* r_ui are star ratings summed over a pair's duplicates, standing in for
  interaction counts;
* pruning: each row's factors from its effective rank on are masked out of
  the prediction and held in the half-step (the paper's early stop,
  carried to ALS by this repository); at rate 0 it is plain iALS;
* a row without pairs keeps its initial factors (Spark has no factor for
  it); initial factors are N(0, 0.1^2), the repository's, where Spark
  draws normalised uniform ones and Hu et al. do not say;
* every row is solved by a direct solve in float32, where Spark uses a
  Cholesky solve in float64 (or NNLS).

``gram`` is the precision of the Gramians' products: "highest", or "bf16"
(one bfloat16 pass: both operands rounded to bfloat16, sums in float32),
the lower-precision control.  ``fault`` plants a fault: "half" leaves out
every other pair of each row, "cap" keeps the first ``CAP`` pairs of each
row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _leaf_norm, _rearrange, init_tables, ranks, table_threshold

WIDTH = 256        # pairs a piece holds
BLOCK_ROWS = 1024  # rows solved together
SLOTS = 1 << 15    # pair slots a Gramian call takes
PAIR_BATCH = 1 << 20
CAP = 4096


def merge(user, item, rating, num_items: int):
    """Distinct (user, item) pairs, sorted, with each pair's summed rating."""
    key = np.asarray(user, np.int64) * num_items + np.asarray(item, np.int64)
    keys, inverse = np.unique(key, return_inverse=True)
    r = np.bincount(inverse, weights=np.asarray(rating, np.float64)).astype(np.float32)
    return (keys // num_items).astype(np.int32), (keys % num_items).astype(np.int32), r


def _fault(row, fault: str):
    """The pairs a fault keeps, of pairs grouped by ``row``."""
    if not fault:
        return np.ones(row.size, bool)
    starts = np.searchsorted(row, row)
    pos = np.arange(row.size) - starts
    if fault == "half":
        return pos % 2 == 0
    if fault == "cap":
        return pos < CAP
    raise ValueError(f"unknown fault {fault!r}")


class Side:
    """One side's pairs grouped by the row they solve, in pieces."""

    def __init__(self, row, col, conf, num_rows: int, fault: str = ""):
        order = np.argsort(row, kind="stable")
        row, col, conf = row[order], col[order], conf[order]
        keep = _fault(row, fault)
        row, col, conf = row[keep], col[keep], conf[keep]
        self.row, self.col, self.conf = row, col, conf
        self.num_rows = num_rows
        self.count = np.bincount(row, minlength=num_rows)
        self.width = min(WIDTH, max(-(-int(self.count.max(initial=1)) // 8) * 8, 8))
        pieces = -(-self.count // self.width)
        self.first_piece = np.concatenate([[0], np.cumsum(pieces)])
        n = int(self.first_piece[-1])
        starts = np.concatenate([[0], np.cumsum(self.count)[:-1]])
        pos = np.arange(row.size) - starts[row]
        at = self.first_piece[row] + pos // self.width
        self.piece_row = np.repeat(np.arange(num_rows), pieces).astype(np.int32)
        self.piece_col = np.zeros((n, self.width), np.int32)
        self.piece_conf = np.zeros((n, self.width), np.float32)
        self.piece_col[at, pos % self.width] = col
        self.piece_conf[at, pos % self.width] = conf


def _round(x, gram: str):
    if gram == "highest":
        return x
    if gram == "bf16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    raise ValueError(f"unknown gram precision {gram!r}")


def _mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision="highest", preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("rows", "gram"))
def _pieces_gram(masked, col, conf, seg, *, rows: int, gram: str):
    """A batch of pieces' sums of (c - 1) y y' and c y, summed into their
    rows ``seg`` of a block (``rows`` drops a piece)."""
    f = masked[col]
    w = jnp.where(conf > 0, conf - 1.0, 0.0)
    a = _mm("nck,ncl->nkl", _round(f * w[..., None], gram), _round(f, gram))
    b = _mm("nck->nk", _round(f * conf[..., None], gram))
    return (jax.ops.segment_sum(a, seg, num_segments=rows + 1)[:rows],
            jax.ops.segment_sum(b, seg, num_segments=rows + 1)[:rows])


@jax.jit
def _solve(a_obs, b, g, old, n, t, lam):
    k = old.shape[1]
    r = ranks(old, t)
    keep = (jnp.arange(k)[None, :] < r[:, None]).astype(jnp.float32)
    inside = keep[:, :, None] * keep[:, None, :]
    eye = jnp.eye(k, dtype=jnp.float32)
    a = (g + a_obs + lam * n[:, None, None] * eye) * inside + eye * (1.0 - inside)
    rhs = b * keep + old * (1.0 - keep)
    x = jnp.linalg.solve(a, rhs[..., None])[..., 0]
    return jnp.where((n > 0)[:, None], x, old)


@functools.partial(jax.jit, static_argnames=("gram",))
def _fixed_gram(fixed, t, *, gram: str):
    masked = fixed * (jnp.arange(fixed.shape[1])[None, :] < ranks(fixed, t)[:, None])
    return masked, _mm("ik,il->kl", _round(masked, gram), _round(masked, gram))


def systems(masked, side: Side, lo: int, hi: int, gram: str = "highest"):
    """Rows ``[lo, hi)``'s sums of (c - 1) y~ y~' and c y~ over their pairs."""
    rows = hi - lo
    p0, p1 = int(side.first_piece[lo]), int(side.first_piece[hi])
    k = masked.shape[1]
    batch = max(SLOTS // side.width, 1)
    a = jnp.zeros((rows, k, k), jnp.float32)
    b = jnp.zeros((rows, k), jnp.float32)
    for s in range(p0, p1, batch):
        e = min(s + batch, p1)
        pad = batch - (e - s)
        col = np.pad(side.piece_col[s:e], ((0, pad), (0, 0)))
        conf = np.pad(side.piece_conf[s:e], ((0, pad), (0, 0)))
        seg = np.pad(side.piece_row[s:e] - lo, (0, pad), constant_values=rows)
        da, db = _pieces_gram(masked, col, conf, seg, rows=rows, gram=gram)
        a, b = a + da, b + db
    return a, b


def half_step(x, fixed, t_x, t_f, side: Side, lam, gram: str = "highest"):
    """Every row of ``x`` with pairs solved against ``fixed``."""
    masked, g = _fixed_gram(fixed, t_f, gram=gram)
    out = []
    count = side.count.astype(np.float32)
    for lo in range(0, side.num_rows, BLOCK_ROWS):
        hi = min(lo + BLOCK_ROWS, side.num_rows)
        a, b = systems(masked, side, lo, hi, gram)
        out.append(_solve(a, b, g, x[lo:hi], count[lo:hi], t_x, lam))
    return jnp.concatenate(out)


@jax.jit
def _observed(pm, qm, u, i, c):
    s = _mm("nk,nk->n", pm[u], qm[i])
    return jnp.sum(jnp.where(c > 0, c * (1.0 - s) ** 2 - s * s, 0.0))


def objective(p, q, t_p, t_q, users: Side, items: Side, lam) -> float:
    """The loss of the module docstring, the users' pairs as the observed
    ones."""
    pm, gp = _fixed_gram(p, t_p, gram="highest")
    qm, gq = _fixed_gram(q, t_q, gram="highest")
    total = float(jnp.sum(gp * gq))
    n = users.row.size
    for s in range(0, n, PAIR_BATCH):
        e = min(s + PAIR_BATCH, n)
        pad = PAIR_BATCH - (e - s)
        total += float(_observed(pm, qm, np.pad(users.row[s:e], (0, pad)),
                                 np.pad(users.col[s:e], (0, pad)),
                                 np.pad(users.conf[s:e], (0, pad))))
    penalty = (np.sum(users.count * np.asarray(jnp.sum(p * p, axis=1), np.float64))
               + np.sum(items.count * np.asarray(jnp.sum(q * q, axis=1), np.float64)))
    return total + float(lam) * float(penalty)


def sides(cfg: dict, train, fault: str = ""):
    """The users' and the items' sides of the training log's pairs."""
    m, n = cfg["num_users"], cfg["num_items"]
    u, i, r = merge(np.asarray(train[0]), np.asarray(train[1]), np.asarray(train[2]), n)
    c = (1.0 + cfg["implicit_alpha"] * r).astype(np.float32)
    return Side(u, i, c, m, fault), Side(i, u, c, n, fault)


def train_readings(cfg: dict, train, seed: int, *, epochs: int = 3,
                   gram: str = "highest", fault: str = ""):
    """Follow the first ``epochs`` epochs of a job from the seed and return
    the readings the comparison uses: the loss over the pairs after each
    epoch, per observed pair (``loss``); P after the first user half-step
    (``p_half``, unpermuted); each table's change after ``epochs`` epochs
    (``change``); and the thresholds and permutation."""
    users, items = sides(cfg, train, fault)
    k, lam = cfg["k"], jnp.float32(cfg["lam"])
    p0, q0 = init_tables(seed, cfg["num_users"], cfg["num_items"], k, cfg["init_scale"])
    p, q = p0, q0
    t_p = t_q = 0.0
    perm = jnp.arange(k)
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for epoch in range(epochs):
            tp, tq = jnp.float32(t_p), jnp.float32(t_q)
            p = half_step(p, q, tp, tq, users, lam, gram)
            if epoch == 0:
                out["p_half"] = np.asarray(p)
            q = half_step(q, p, tq, tp, items, lam, gram)
            out["loss"].append(objective(p, q, tp, tq, users, items, lam) / users.row.size)
            if epoch == 0:
                t_p = table_threshold(p, cfg["pruning_rate"])
                t_q = table_threshold(q, cfg["pruning_rate"])
                perm = _rearrange(p, q, t_p, t_q)
                p, q = p[:, perm], q[:, perm]
    out["change"] = {"p": _leaf_norm(p - p0[:, perm]), "q": _leaf_norm(q - q0[:, perm])}
    out["t_p"], out["t_q"], out["perm"] = t_p, t_q, np.asarray(perm)
    return out


def row_gap(prog, ref) -> float:
    """The worst row's relative error, ||prog_u - ref_u|| / ||ref_u||."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    err = np.linalg.norm(prog - ref, axis=1)
    norm = np.linalg.norm(ref, axis=1)
    return float(np.max(np.where(err == 0, 0.0, err / np.maximum(norm, 1e-30)), initial=0.0))


def loss_gap(prog, ref) -> float:
    return max(abs(a - b) / abs(b) for a, b in zip(prog, ref))

