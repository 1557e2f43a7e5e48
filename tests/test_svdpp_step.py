"""One SVD++ training step against Koren's update (KDD 2008, section 4),
computed by hand in float64 on three ratings of two users.

The step is a minibatch: every rating's gradient reads the parameters as
they were before the batch, and the updates of a row add up.  Plain SGD,
no pruning (thresholds 0), so each update is -lr times the gradient:

    e    = r - (mu + b_u + b_i + q_i . z_u),  z_u = p_u + |N(u)|^-1/2 sum_{j in N(u)} y_j
    p_u += lr (e q_i - lam p_u)
    q_i += lr (e z_u - lam q_i)
    y_j += lr (e |N(u)|^-1/2 q_i - lam y_j)   for j in N(u)
    b_u += lr (e - lam b_u),  b_i += lr (e - lam b_i)
"""
import jax.numpy as jnp
import numpy as np

from repro.core import mf
from repro.optim.optimizers import RowOptimizer

LR, LAM, MU = 0.1, 0.02, 3.0
P = np.array([[0.1, 0.2], [0.3, -0.1]])
Q = np.array([[0.2, 0.1], [-0.1, 0.3], [0.05, 0.2]])
Y = np.array([[0.1, 0.0], [0.0, 0.2], [0.3, 0.1], [0.0, 0.0]])   # row 3 pads
B_U, B_I = np.array([0.5, -0.2]), np.array([0.1, 0.0, -0.3])
RATINGS = [(0, 0, 4.0), (0, 1, 3.0), (1, 2, 5.0)]
N = {0: [0, 1], 1: [2]}
HIST = np.array([[0, 1], [2, 3]], np.int32)


def _koren():
    p, q, y, b_u, b_i = P.copy(), Q.copy(), Y.copy(), B_U.copy(), B_I.copy()
    for u, i, r in RATINGS:
        norm = len(N[u]) ** -0.5
        z = P[u] + norm * Y[N[u]].sum(axis=0)
        e = r - (MU + B_U[u] + B_I[i] + z @ Q[i])
        p[u] += LR * (e * Q[i] - LAM * P[u])
        q[i] += LR * (e * z - LAM * Q[i])
        for j in N[u]:
            y[j] += LR * (e * norm * Q[i] - LAM * Y[j])
        b_u[u] += LR * (e - LAM * B_U[u])
        b_i[i] += LR * (e - LAM * B_I[i])
    return p, q, y, b_u, b_i


def test_svdpp_step_is_korens_update():
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    params = mf.MFParams(f32(P), f32(Q), f32(B_U[:, None]), f32(B_I[:, None]),
                         f32(MU), f32(Y))
    opt = RowOptimizer(name="sgd")
    users = np.array([u for u, _, _ in RATINGS], np.int32)
    batch = {"user": jnp.asarray(users),
             "item": jnp.asarray([i for _, i, _ in RATINGS], jnp.int32),
             "rating": f32([r for _, _, r in RATINGS]),
             "hist": jnp.asarray(HIST[users])}
    new, _, _ = mf.train_step(
        params, mf.init_opt_state(params, opt), batch, f32(0.0), f32(0.0),
        f32(LR), jnp.ones((2,), jnp.float32), opt=opt, lam=LAM,
    )
    got = (new.p, new.q, new.implicit, new.user_bias[:, 0], new.item_bias[:, 0])
    # float32 arithmetic on values of order 1: a few ulp of 1
    for g, w in zip(got, _koren()):
        np.testing.assert_allclose(np.asarray(g), w, rtol=0, atol=1e-6)
    # the penalty on the whole user vector z_u moves p_0 by lr*lam*(z_0 -
    # p_0) per rating, 2.8e-4 here: far outside that tolerance
    z0 = P[0] + (Y[0] + Y[1]) / np.sqrt(2)
    assert np.abs(2 * LR * LAM * (z0 - P[0])).max() > 100 * 1e-6
