"""Optimizers.

Two families:

* **Row optimizers** — the MF/embedding path.  State lives alongside the
  (rows, k) table; updates touch only gathered rows.  All of them accept the
  paper's pruning ``mask`` so Algorithm 3's truncated update composes with
  any optimizer (paper §5.3 shows the method is optimizer-agnostic; we
  implement SGD, momentum, Adagrad — LibMF's default — AdaDelta and Adam).
  SGD and Adagrad, whose every write is additive, go through
  :func:`add_rows`: the batch's occurrences are sorted by row id, the
  updates of each id are summed on the device by a segmented scan, and
  each touched row is written once, into every table of that side, by a
  kernel that only streams row DMAs (``kernels/row_write.py``).
  Duplicates are summed there, in the scan's float32 order, where XLA's
  scatter on the TPU would apply them one update row at a time.
  Momentum, AdaDelta and Adam keep their per-occurrence scatters
  (``.at[].add`` for the rows, last-write ``.at[].set`` for the state).
* **Dense optimizers** — pytree-wide Adam/SGD for the non-MF architectures
  (transformers, GNN, recsys MLPs).

All functions are jit-safe and shard-transparent: they are elementwise or
gather/scatter ops, so SPMD partitioning propagates table shardings into the
optimizer state untouched.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


# ---------------------------------------------------------------------------
# Row optimizers (embedding tables / factor matrices)
# ---------------------------------------------------------------------------


def _row_runs(idx: jax.Array, live: jax.Array, num_rows: int):
    """The batch's occurrences in row-id order, and its runs of one id.

    Returns ``(order, rows, head)``: ``order`` sorts the occurrences stably
    by id, with those not ``live`` last under the key ``num_rows``;
    ``head`` marks each run's first position, and every occurrence that is
    not live is a run of its own; ``rows`` holds a run's id at its last
    position and a distinct id past the table everywhere else, so a write
    at ``rows`` touches each live row once and skips the rest.
    """
    size = idx.shape[0]
    pos = jnp.arange(size, dtype=jnp.int32)
    key = jnp.where(live, idx.astype(jnp.int32), num_rows)
    key, order = jax.lax.sort((key, pos), num_keys=1, is_stable=True)
    change = key[1:] != key[:-1]
    inside = key < num_rows
    head = jnp.concatenate([jnp.ones((1,), bool), change]) | ~inside
    tail = jnp.concatenate([change, jnp.ones((1,), bool)]) & inside
    return order, jnp.where(tail, key, num_rows + pos), head


def _run_sums(x: jax.Array, head: jax.Array) -> jax.Array:
    """Inclusive sums of ``x`` (B, w) along each run that ``head`` starts.

    Hillis-Steele doubling: at distance ``d`` a position adds the partial
    sum ``d`` back when that position lies in its own run.  A run's last
    position ends up holding the sum of the whole run; a run of one keeps
    its value bit for bit.  The doubling stops at the longest run, which
    on the user side of a batch is a few occurrences."""
    size = x.shape[0]
    pos = jnp.arange(size, dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(head, pos, 0))
    longest = jnp.max(pos - start) + 1

    def level(carry):
        x, d = carry
        same = (pos >= d) & (jnp.roll(start, d) == start)
        return jnp.where(same[:, None], x + jnp.roll(x, d, axis=0), x), 2 * d

    x, _ = jax.lax.while_loop(
        lambda carry: carry[1] < longest, level, (x, jnp.int32(1))
    )
    return x


def rows_written(idx: jax.Array, live: jax.Array, num_rows: int) -> jax.Array:
    """How many distinct rows :func:`add_rows` writes for ``idx``/``live``.

    Built from the same sort as the write, so in one program XLA computes
    it once for both."""
    _, rows, _ = _row_runs(idx, live, num_rows)
    return jnp.sum(rows < num_rows)


def add_rows(
    tables: Tuple[jax.Array, ...],
    idx: jax.Array,                   # (B,) row ids, duplicates allowed
    updates: Tuple[jax.Array, ...],   # per table, (B, width) per occurrence
    live: jax.Array,                  # (B,) bool; False = adds nothing
) -> Tuple[jax.Array, ...]:
    """``tables[t][idx[b]] += updates[t][b]`` for every occurrence ``b``,
    writing each touched row of each table once.

    One stable sort of the ids serves every table.  A segmented scan sums
    each run of one id (a prefix sum with a difference would cancel in
    float32, and ``segment_sum`` is itself a conflicting scatter); the row
    as it was before the batch, read per occurrence, plus its run's sum is
    written once by ``kernels/row_write.py``, one row DMA per touched row
    and table.  Only the float32 order in which duplicates are summed
    differs from sequential adds.  Occurrences that are not ``live`` (their
    updates are exact zeros) are left out, so adding or removing them
    changes no bit.
    """
    # imported here: the kernels package imports this module via core.mf
    from repro.kernels.row_write import write_rows

    num_rows, n = tables[0].shape[0], len(tables)
    order, rows, head = _row_runs(idx, live, num_rows)
    cuts = list(itertools.accumulate([t.shape[-1] for t in tables] * 2))[:-1]
    # one row permutation puts every occurrence's updates, and its row as it
    # was before the batch, in id order
    moved = jnp.split(
        jnp.concatenate(
            [u.astype(t.dtype) for u, t in zip(updates, tables)]
            + [t[idx] for t in tables],
            axis=-1,
        )[order],
        cuts,
        axis=-1,
    )
    sums = jnp.split(
        _run_sums(jnp.concatenate(moved[:n], axis=-1), head), cuts[: n - 1], axis=-1
    )
    return write_rows(
        rows, tuple(cur + s for cur, s in zip(moved[n:], sums)), tuple(tables)
    )


@dataclasses.dataclass(frozen=True)
class RowOptimizer:
    """Interface: ``init(param) -> state``;  ``apply_rows`` returns updates."""

    name: str = "sgd"
    eps: float = 1e-8
    rho: float = 0.95     # adadelta decay
    beta1: float = 0.9    # adam
    beta2: float = 0.999  # adam
    mu: float = 0.9       # momentum

    def init(self, param: jax.Array) -> Dict[str, jax.Array]:
        zeros = lambda: jnp.zeros_like(param)  # noqa: E731
        if self.name == "sgd":
            return {}
        if self.name == "momentum":
            return {"mom": zeros()}
        if self.name == "adagrad":
            return {"acc": zeros()}
        if self.name == "adadelta":
            return {"eg2": zeros(), "edx2": zeros()}
        if self.name == "adam":
            return {"m": zeros(), "v": zeros(), "t": jnp.zeros((), jnp.int32)}
        raise ValueError(f"unknown row optimizer {self.name!r}")

    def apply_rows(
        self,
        param: jax.Array,
        state: Dict[str, jax.Array],
        idx: jax.Array,        # (B,) row indices (duplicates allowed)
        grad_rows: jax.Array,  # (B, k) gradient of the gathered rows
        mask: jax.Array,       # (B, k) 0/1 pruning mask (Alg. 3); 1s = update
        lr: float | jax.Array,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        g = grad_rows.astype(jnp.float32) * mask
        live = jnp.any(mask != 0, axis=-1)
        if self.name == "sgd":
            (param,) = add_rows((param,), idx, ((-lr * g).astype(param.dtype),), live)
            return param, state

        if self.name == "momentum":
            # Heavy ball on the masked gradient.  Like adadelta/adam,
            # duplicate rows collapse to the last write and an all-zero mask
            # still decays + writes back the row's momentum — zero-weight
            # rows gate the param update, not the state (mf.train_step NB).
            mom_rows = self.mu * state["mom"][idx] + g
            return (
                param.at[idx].add((-lr * mom_rows * mask).astype(param.dtype)),
                {"mom": state["mom"].at[idx].set(mom_rows)},
            )

        if self.name == "adagrad":
            # each occurrence's delta reads the pre-batch accumulator
            acc_rows = state["acc"][idx] + g * g
            delta = -lr * g / jnp.sqrt(acc_rows + self.eps) * mask
            param, acc = add_rows(
                (param, state["acc"]), idx, (delta.astype(param.dtype), g * g), live
            )
            return param, {"acc": acc}

        if self.name == "adadelta":
            eg2_rows = self.rho * state["eg2"][idx] + (1 - self.rho) * g * g
            dx = (
                -jnp.sqrt(state["edx2"][idx] + self.eps)
                / jnp.sqrt(eg2_rows + self.eps)
                * g
            ) * mask
            edx2_rows = self.rho * state["edx2"][idx] + (1 - self.rho) * dx * dx
            # EMA state is written back per-row (set, not add): duplicates in a
            # batch collapse to the last occurrence, matching sequential SGD up
            # to batch reordering.
            return (
                param.at[idx].add(dx.astype(param.dtype)),
                {
                    "eg2": state["eg2"].at[idx].set(eg2_rows),
                    "edx2": state["edx2"].at[idx].set(edx2_rows),
                },
            )

        if self.name == "adam":
            t = state["t"] + 1
            m_rows = self.beta1 * state["m"][idx] + (1 - self.beta1) * g
            v_rows = self.beta2 * state["v"][idx] + (1 - self.beta2) * g * g
            mhat = m_rows / (1 - self.beta1 ** t.astype(jnp.float32))
            vhat = v_rows / (1 - self.beta2 ** t.astype(jnp.float32))
            delta = -lr * mhat / (jnp.sqrt(vhat) + self.eps) * mask
            return (
                param.at[idx].add(delta.astype(param.dtype)),
                {
                    "m": state["m"].at[idx].set(m_rows),
                    "v": state["v"].at[idx].set(v_rows),
                    "t": t,
                },
            )
        raise ValueError(f"unknown row optimizer {self.name!r}")


# ---------------------------------------------------------------------------
# Dense optimizers (full-model pytrees)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Adam:
    lr: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0

    def init(self, params: Pytree) -> Pytree:
        zeros = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        return {
            "m": zeros,
            "v": jax.tree_util.tree_map(jnp.zeros_like, zeros),
            "t": jnp.zeros((), jnp.int32),
        }

    def apply(self, params: Pytree, state: Pytree, grads: Pytree, lr_scale=1.0):
        t = state["t"] + 1
        tf = t.astype(jnp.float32)
        b1c = 1 - self.beta1 ** tf
        b2c = 1 - self.beta2 ** tf

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m = self.beta1 * m + (1 - self.beta1) * g
            v = self.beta2 * v + (1 - self.beta2) * g * g
            step = self.lr * lr_scale * (m / b1c) / (jnp.sqrt(v / b2c) + self.eps)
            if self.weight_decay:
                step = step + self.lr * lr_scale * self.weight_decay * p.astype(
                    jnp.float32
                )
            return (p.astype(jnp.float32) - step).astype(p.dtype), m, v

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        return new_p, {"m": new_m, "v": new_v, "t": t}


@dataclasses.dataclass(frozen=True)
class Sgd:
    lr: float = 1e-2
    momentum: float = 0.0

    def init(self, params: Pytree) -> Pytree:
        if self.momentum == 0.0:
            return {}
        return {
            "mom": jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, jnp.float32), params
            )
        }

    def apply(self, params: Pytree, state: Pytree, grads: Pytree, lr_scale=1.0):
        if self.momentum == 0.0:
            new_p = jax.tree_util.tree_map(
                lambda p, g: (p - self.lr * lr_scale * g.astype(p.dtype)).astype(
                    p.dtype
                ),
                params,
                grads,
            )
            return new_p, state

        def upd(p, g, m):
            m = self.momentum * m + g.astype(jnp.float32)
            return (p.astype(jnp.float32) - self.lr * lr_scale * m).astype(p.dtype), m

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["mom"])
        out = [upd(p, g, m) for p, g, m in zip(flat_p, flat_g, flat_m)]
        return (
            treedef.unflatten([o[0] for o in out]),
            {"mom": treedef.unflatten([o[1] for o in out])},
        )
