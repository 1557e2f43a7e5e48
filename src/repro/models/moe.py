"""Mixture-of-Experts FFN with sort-based (dropping) dispatch and EP sharding.

Dispatch is the static-shape sort/permute formulation (no (T, E, C) one-hot):
tokens are ordered by assigned expert, placed into per-expert capacity
buffers, processed by a batched expert einsum (experts shardable over the
"model" mesh axis — EP), and combined back with gate weights.  Overflowing
tokens are dropped (standard GShard-style capacity semantics); shared experts
(DeepSeek-style) bypass routing entirely.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import gated_mlp


class MoEConfig(NamedTuple):
    num_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden
    num_shared: int = 0            # always-on experts (DeepSeek)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01


def init_moe_params(
    rng, d_model: int, cfg: MoEConfig, *, activation: str = "swiglu", dtype=jnp.float32
) -> Dict[str, jax.Array]:
    ks = jax.random.split(rng, 5)
    e, f = cfg.num_experts, cfg.d_ff
    scale_in = d_model ** -0.5
    scale_out = f ** -0.5
    params = {
        "router": scale_in * jax.random.normal(ks[0], (d_model, e), jnp.float32),
        "wg": scale_in * jax.random.normal(ks[1], (e, d_model, f), dtype),
        "wi": scale_in * jax.random.normal(ks[2], (e, d_model, f), dtype),
        "wo": scale_out * jax.random.normal(ks[3], (e, f, d_model), dtype),
    }
    if cfg.num_shared:
        sf = cfg.num_shared * f
        kg, ki, ko = jax.random.split(ks[4], 3)
        params["shared"] = {
            "wg": scale_in * jax.random.normal(kg, (d_model, sf), dtype),
            "wi": scale_in * jax.random.normal(ki, (d_model, sf), dtype),
            "wo": sf ** -0.5 * jax.random.normal(ko, (sf, d_model), dtype),
        }
    return params


def _capacity(num_tokens: int, cfg: MoEConfig) -> int:
    cap = int(num_tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(cap, cfg.top_k)


def moe_ffn(
    x: jax.Array,
    params: Dict[str, jax.Array],
    cfg: MoEConfig,
    *,
    activation: str = "swiglu",
    use_shard_map: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Dispatch entry point.  ``use_shard_map`` selects the replicated-
    dispatch EP formulation (moe_ffn_shard_map) when an ambient mesh with a
    "model" axis is set; otherwise falls back to the XLA-SPMD path."""
    if use_shard_map:
        am = jax.sharding.get_abstract_mesh()
        if (
            "model" in am.axis_names
            and cfg.num_experts % am.shape["model"] == 0
        ):
            return moe_ffn_shard_map(x, params, cfg, activation=activation, mesh=am)
    return moe_ffn_xla(x, params, cfg, activation=activation)


def moe_ffn_xla(
    x: jax.Array,  # (T, d) flattened tokens
    params: Dict[str, jax.Array],
    cfg: MoEConfig,
    *,
    activation: str = "swiglu",
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (T, d), aux_loss ()) — aux is the standard load-balance
    loss (mean fraction * mean router prob per expert, scaled by E)."""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    cap = _capacity(t, cfg)

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Load-balance aux loss (Switch/GShard form).
    density = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32), axis=1), axis=0
    ) / k
    router_mean = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_weight * e * jnp.sum(density * router_mean)

    # ---- sort-based dispatch -------------------------------------------
    flat_expert = expert_ids.reshape(-1)              # (T*k,)
    flat_token = jnp.arange(t * k, dtype=jnp.int32) // k
    flat_gate = gate_vals.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)     # group by expert
    sorted_expert = flat_expert[order]
    counts = jnp.bincount(flat_expert, length=e)
    starts = jnp.cumsum(counts) - counts              # exclusive prefix
    pos_in_expert = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_expert]

    keep = pos_in_expert < cap
    slot = jnp.where(keep, sorted_expert * cap + pos_in_expert, e * cap)

    # Scatter tokens into (E*cap + 1, d); the extra row absorbs drops.
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[slot].set(x[flat_token[order]])
    buf = buf[: e * cap].reshape(e, cap, d)

    # ---- expert compute (EP: leading axis shards over "model") ---------
    def expert_fn(xb, wg, wi, wo):
        return gated_mlp(xb, {"wg": wg, "wi": wi, "wo": wo}, activation)

    out_buf = jax.vmap(expert_fn)(buf, params["wg"], params["wi"], params["wo"])
    out_buf = jnp.concatenate(
        [out_buf.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], axis=0
    )

    # ---- combine ---------------------------------------------------------
    gathered = out_buf[slot] * (flat_gate[order] * keep.astype(jnp.float32))[
        :, None
    ].astype(x.dtype)
    combined = jnp.zeros((t, d), x.dtype).at[flat_token[order]].add(gathered)

    if "shared" in params:
        combined = combined + gated_mlp(x, params["shared"], activation)
    return combined, aux


# ---------------------------------------------------------------------------
# shard_map replicated-dispatch EP (§Perf iteration 1)
# ---------------------------------------------------------------------------
#
# The XLA-SPMD lowering of the sort/scatter dispatch materializes the
# (T*top_k, d) gathered-token buffers REPLICATED along the model axis and
# all-reduces them (~50 GB each at the deepseek train_4k shape — measured in
# EXPERIMENTS.md §Perf).  But with tokens sharded over the data axes, every
# model rank already holds a full copy of its data shard's tokens, so expert
# parallelism needs no token exchange at all:
#
#   * each model rank routes its local token block (routing is cheap),
#   * keeps only the pairs whose expert lives in its local expert slab,
#   * runs its local experts,
#   * and ONE psum over "model" combines the partial outputs (each token's
#     top-k experts live on <= k ranks; other ranks contribute zeros).
#
# Collectives per layer drop from O(T*k*d) all-reduces to a single (T_loc, d)
# psum — ~300x less ICI traffic at the deepseek shape.  Capacity becomes
# per-data-shard (the standard formulation in real EP systems).


def moe_ffn_shard_map(
    x: jax.Array,  # (T, d)
    params: Dict[str, jax.Array],
    cfg: MoEConfig,
    *,
    activation: str = "swiglu",
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P

    mesh = mesh if mesh is not None else jax.sharding.get_abstract_mesh()
    if mesh.empty:
        raise ValueError(
            "moe_ffn_shard_map needs a mesh: pass mesh= or enter a "
            "jax.sharding.set_mesh(...) context"
        )
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_model = mesh.shape["model"]
    e_loc = cfg.num_experts // n_model
    e, k = cfg.num_experts, cfg.top_k

    def body(x_blk, router, wg, wi, wo):
        t_loc, d = x_blk.shape
        cap = max(int(t_loc * k * cfg.capacity_factor / e), k)

        logits = jnp.einsum("td,de->te", x_blk.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        density = jnp.mean(
            jnp.sum(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32), axis=1),
            axis=0,
        ) / k
        router_mean = jnp.mean(probs, axis=0)
        aux = cfg.router_aux_weight * e * jnp.sum(density * router_mean)
        if dp:
            aux = jax.lax.pmean(aux, dp)

        flat_expert = expert_ids.reshape(-1)
        flat_token = jnp.arange(t_loc * k, dtype=jnp.int32) // k
        flat_gate = gate_vals.reshape(-1)

        order = jnp.argsort(flat_expert, stable=True)
        sorted_expert = flat_expert[order]
        counts = jnp.bincount(flat_expert, length=e)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t_loc * k, dtype=jnp.int32) - starts[sorted_expert]

        off = jax.lax.axis_index("model").astype(jnp.int32) * e_loc
        local = (sorted_expert >= off) & (sorted_expert < off + e_loc)
        keep = local & (pos < cap)
        slot = jnp.where(keep, (sorted_expert - off) * cap + pos, e_loc * cap)

        buf = jnp.zeros((e_loc * cap + 1, d), x_blk.dtype)
        buf = buf.at[slot].set(x_blk[flat_token[order]])
        buf = buf[: e_loc * cap].reshape(e_loc, cap, d)

        def expert_fn(xb, g, i, o):
            return gated_mlp(xb, {"wg": g, "wi": i, "wo": o}, activation)

        out_buf = jax.vmap(expert_fn)(buf, wg, wi, wo)
        out_buf = jnp.concatenate(
            [out_buf.reshape(e_loc * cap, d), jnp.zeros((1, d), x_blk.dtype)],
            axis=0,
        )
        gathered = out_buf[slot].astype(jnp.float32) * (
            flat_gate[order] * keep.astype(jnp.float32)
        )[:, None]
        out_loc = (
            jnp.zeros((t_loc, d), jnp.float32)
            .at[flat_token[order]]
            .add(gathered)
        )
        out = jax.lax.psum(out_loc, "model").astype(x_blk.dtype)
        return out, aux[None]

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp if dp else None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(dp if dp else None, None), P(None)),
        check_vma=False,
    )(x, params["router"], params["wg"], params["wi"], params["wo"])
    combined = out
    if "shared" in params:
        combined = combined + gated_mlp(x, params["shared"], activation)
    return combined, aux[0]
