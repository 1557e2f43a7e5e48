"""dpmf — the paper's own architecture at production scale.

FunkSVD factorization of a 100M-user x 10M-item rating matrix at k=128,
trained with dynamically-pruned minibatch SGD/Adagrad (the paper's full
pipeline), user rows sharded over the data axes and item rows over "model"
(DESIGN.md §3).  Not one of the 10 assigned archs — it is the paper's model
itself, included per the deliverables ("+ paper's own")."""
import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.configs import base
from repro.core import mf
from repro.distributed import sharding as shd
from repro.optim.optimizers import RowOptimizer

ARCH_ID = "dpmf"


@dataclasses.dataclass(frozen=True)
class DPMFConfig:
    name: str = ARCH_ID
    num_users: int = 100_000_000
    num_items: int = 10_000_000
    k: int = 128
    lam: float = 0.02
    lr: float = 0.05
    optimizer: str = "adagrad"
    pruning_rate: float = 0.3


CONFIG = DPMFConfig()


def smoke_config() -> DPMFConfig:
    return DPMFConfig(name=ARCH_ID + "-smoke", num_users=200, num_items=150, k=16)


def _train_cell(batch: int) -> base.CellSpec:
    cfg = CONFIG
    opt = RowOptimizer(name=cfg.optimizer)

    def init(rng):
        return mf.init_params(rng, cfg.num_users, cfg.num_items, cfg.k)

    dim_mask = jnp.ones((cfg.k,), jnp.float32)

    def step(params, opt_state, batch_d, t_p, t_q):
        return mf.train_step(
            params, opt_state, batch_d, t_p, t_q, jnp.float32(cfg.lr), dim_mask,
            opt=opt, lam=cfg.lam,
        )

    a_params = base.abstract_like(init, jax.random.PRNGKey(0))
    a_opt = base.abstract_like(functools.partial(mf.init_opt_state, opt=opt), a_params)
    a_batch = {
        "user": jax.ShapeDtypeStruct((batch,), jnp.int32),
        "item": jax.ShapeDtypeStruct((batch,), jnp.int32),
        "rating": jax.ShapeDtypeStruct((batch,), jnp.float32),
    }
    a_scalar = jax.ShapeDtypeStruct((), jnp.float32)

    def in_shardings(mesh):
        spec_fn = shd.mf_spec_fn(mesh)
        p_sh = shd.tree_shardings(a_params, spec_fn, mesh)
        # MFOptState paths start with the same field names (p/q/...) so the
        # same spec function shards the accumulators like their tables.
        o_sh = shd.tree_shardings(a_opt, spec_fn, mesh)
        b_sh = shd.mf_batch_shardings(mesh)
        return (p_sh, o_sh, b_sh, shd.replicated(mesh), shd.replicated(mesh))

    return base.CellSpec(
        arch=ARCH_ID,
        shape_id=f"train_{batch // 1024}k",
        kind="train",
        step_fn=step,
        abstract_args=(a_params, a_opt, a_batch, a_scalar, a_scalar),
        in_shardings=in_shardings,
        donate_argnums=(0, 1),
        note="paper's DP-MF minibatch step: gather -> pruned dot -> masked update",
    )


def _serve_cell(batch: int) -> base.CellSpec:
    cfg = CONFIG

    def init(rng):
        return mf.init_params(rng, cfg.num_users, cfg.num_items, cfg.k)

    def step(params, users, t_p, t_q):
        h = params.p[users]
        from repro.core.ranks import mask_rows

        scores = jnp.einsum(
            "bk,nk->bn", mask_rows(h, t_p), mask_rows(params.q, t_q)
        )
        return jax.lax.top_k(scores, 100)

    a_params = base.abstract_like(init, jax.random.PRNGKey(0))
    a_users = jax.ShapeDtypeStruct((batch,), jnp.int32)
    a_scalar = jax.ShapeDtypeStruct((), jnp.float32)

    def in_shardings(mesh):
        p_sh = shd.tree_shardings(a_params, shd.mf_spec_fn(mesh), mesh)
        return (p_sh, shd.ns(mesh, shd.data_axes(mesh)),
                shd.replicated(mesh), shd.replicated(mesh))

    return base.CellSpec(
        arch=ARCH_ID,
        shape_id=f"serve_top100_{batch}",
        kind="serve",
        step_fn=step,
        abstract_args=(a_params, a_users, a_scalar, a_scalar),
        in_shardings=in_shardings,
        note="pruned full-catalog scoring (paper's 'matrix multiplication' stage)",
    )


def _train_cell_owner_compute(batch: int, compress: bool = False) -> base.CellSpec:
    """Beyond-paper §Perf cell: owner-compute shard_map step (bit-exact to
    train_1m; collectives reduced ~10x — see core/mf.train_step_shard_map).
    ``compress`` additionally int8-quantizes the cross-link payloads."""
    cfg = CONFIG
    opt = RowOptimizer(name=cfg.optimizer)

    def init(rng):
        return mf.init_params(rng, cfg.num_users, cfg.num_items, cfg.k)

    def step(params, opt_state, batch_d, t_p, t_q):
        return mf.train_step_shard_map(
            params, opt_state, batch_d, t_p, t_q,
            lr=cfg.lr, lam=cfg.lam, opt_name=cfg.optimizer,
            grad_compression="int8" if compress else "none",
        )

    a_params = base.abstract_like(init, jax.random.PRNGKey(0))
    a_opt = base.abstract_like(functools.partial(mf.init_opt_state, opt=opt), a_params)
    a_batch = {
        "user": jax.ShapeDtypeStruct((batch,), jnp.int32),
        "item": jax.ShapeDtypeStruct((batch,), jnp.int32),
        "rating": jax.ShapeDtypeStruct((batch,), jnp.float32),
    }
    a_scalar = jax.ShapeDtypeStruct((), jnp.float32)

    def in_shardings(mesh):
        spec_fn = shd.mf_spec_fn(mesh)
        return (
            shd.tree_shardings(a_params, spec_fn, mesh),
            shd.tree_shardings(a_opt, spec_fn, mesh),
            shd.mf_batch_shardings(mesh),
            shd.replicated(mesh),
            shd.replicated(mesh),
        )

    return base.CellSpec(
        arch=ARCH_ID,
        shape_id=f"train_{batch // 1024}k_sm" + ("c" if compress else ""),
        kind="train",
        step_fn=step,
        abstract_args=(a_params, a_opt, a_batch, a_scalar, a_scalar),
        in_shardings=in_shardings,
        donate_argnums=(0, 1),
        note="owner-compute shard_map DP-MF step (§Perf; batch routed by user shard)",
    )


def cells():
    return {
        "train_1m": lambda: _train_cell(1_048_576),
        "train_1m_sm": lambda: _train_cell_owner_compute(1_048_576),
        "train_1m_smc": lambda: _train_cell_owner_compute(1_048_576, compress=True),
        "serve_top100": lambda: _serve_cell(1024),
    }
