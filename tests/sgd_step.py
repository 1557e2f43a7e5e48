"""The training step under plain SGD, driven on gathered rows, for the
tests that hold it to ``kernels.ref.sgd_step_ref``.

Row j of ``p``/``q`` is user j / item j, so every id in the batch is
distinct and the minibatch step is exactly the oracle's one-row update.
"""
import jax.numpy as jnp

from repro.core import mf
from repro.optim.optimizers import RowOptimizer


def train_step_on_rows(p, q, ratings, t, *, lr, lam, bias_u=None,
                       bias_i=None, global_mean=0.0, weight=None):
    """``mf.train_step`` on a one-row-per-rating model.  Returns
    ``(new_p, new_q, new_bias_u, new_bias_i, abs_err)`` — the biases None
    when not given, ``abs_err`` the step's weighted mean |err|."""
    b, k = p.shape
    has_bias = bias_u is not None
    params = mf.MFParams(
        p=p, q=q,
        user_bias=bias_u[:, None] if has_bias else None,
        item_bias=bias_i[:, None] if has_bias else None,
        global_mean=jnp.float32(global_mean) if has_bias else None,
        implicit=None,
    )
    opt = RowOptimizer(name="sgd")
    ids = jnp.arange(b, dtype=jnp.int32)
    batch = {"user": ids, "item": ids, "rating": ratings}
    if weight is not None:
        batch["weight"] = weight
    new, _, metrics = mf.train_step(
        params, mf.init_opt_state(params, opt), batch, jnp.float32(t),
        jnp.float32(t), jnp.float32(lr), jnp.ones((k,)), opt=opt, lam=lam,
    )
    return (
        new.p, new.q,
        new.user_bias[:, 0] if has_bias else None,
        new.item_bias[:, 0] if has_bias else None,
        metrics["abs_err"],
    )


def weighted_mean_abs(err, weight=None):
    """The step's ``abs_err`` metric computed from the oracle's errors."""
    w = jnp.ones_like(err) if weight is None else weight
    return jnp.sum(jnp.abs(err) * w) / jnp.maximum(jnp.sum(w), 1e-9)
