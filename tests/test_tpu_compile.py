"""Compile rehearsals for a described TPU v5e: the main path's Pallas kernels
and training programs at real widths, compiled by the TPU compiler for a
chip that is described, not attached.

Interpret-mode parity tests cannot see what only the chip's compiler
refuses: slices not aligned to the tiling, more VMEM than a kernel may use,
a program that cannot be partitioned.  Nothing here runs; each test compiles
and checks that the Pallas kernel (``tpu_custom_call``) is in the program.

The topology is described inside the module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
import every test file.  All these compiles live in this one file so that
one worker loads it.  ``interpret=False`` is passed explicitly because the
default backend here is the CPU.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import mf
from repro.kernels.fused_mf_sgd import fused_mf_sgd_padded
from repro.kernels.pruned_matmul import pruned_matmul_padded
from repro.kernels.pruned_topk import pruned_topk_padded
from repro.kernels.row_write import write_rows
from repro.launch.mesh import make_mesh
from repro.optim.optimizers import RowOptimizer

K = 128                       # dpmf config width
NETFLIX = (480_189, 17_770)   # users x items of the Netflix Prize set


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host.  The persistent compilation cache is off
    around these compiles: an entry written for a described chip cannot be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # else the compiler logs to /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as exc:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract(tree, sharding_fn):
    """ShapeDtypeStructs of ``tree`` with ``sharding_fn(leaf)`` placements."""
    return jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, sharding_fn(x)), tree
    )


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "m,n,topk",
    [(1024, 65_536, 10), (1024, 65_536, 100), (256, 340_736, 100)],
    ids=["10", "100", "bookx_launch"],
)
def test_pruned_topk_compiles(one_chip, m, n, topk):
    """The scoring kernel, its merge loop's traced trip count included;
    ``bookx_launch`` is one 256-user launch over the BookCrossings catalog
    as the engine pads it (340,554 items to 256-item tiles)."""
    f32, i32 = jnp.float32, jnp.int32
    compiled = pruned_topk_padded.lower(
        _sds((m, K), f32, one_chip), _sds((n, K), f32, one_chip),
        _sds((m, 1), i32, one_chip), _sds((n, 1), i32, one_chip),
        _sds((n, 1), f32, one_chip),
        topk=topk, n_items=n, interpret=False,
    ).compile()
    assert _has_kernel(compiled)


def test_pruned_matmul_compiles(one_chip):
    m, n = 1024, 65_536
    compiled = pruned_matmul_padded.lower(
        _sds((m, K), jnp.float32, one_chip), _sds((n, K), jnp.float32, one_chip),
        _sds((m, 1), jnp.int32, one_chip), _sds((n, 1), jnp.int32, one_chip),
        interpret=False,
    ).compile()
    assert _has_kernel(compiled)


def test_fused_mf_sgd_compiles(one_chip):
    b = 65_536
    rows = _sds((b, K), jnp.float32, one_chip)
    col = _sds((b, 1), jnp.float32, one_chip)
    scalar = _sds((1, 1), jnp.float32, one_chip)
    compiled = fused_mf_sgd_padded.lower(
        rows, rows, col, col, col, col, scalar, scalar, scalar,
        lr=0.05, lam=0.02, interpret=False,
    ).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize(
    "rows", [162_541, 62_423], ids=["ml25m_users", "ml25m_items"]
)
def test_write_rows_compiles(one_chip, rows):
    """The training step's row write at the ml25m_k128 tables: a factor
    table and its Adagrad table, in place, for a 4,096-rating batch."""
    b = 4096
    compiled = write_rows.lower(
        _sds((b,), jnp.int32, one_chip),
        (_sds((b, K), jnp.float32, one_chip),) * 2,
        (_sds((rows, K), jnp.float32, one_chip),) * 2,
        interpret=False,
    ).compile()
    assert _has_kernel(compiled)


def test_train_epoch_scan_fused_compiles(one_chip):
    """The trainer's whole epoch program (SGD + fused kernel) at the
    Netflix shape: the kernel inside the donated scan, and the tables fit."""
    m, n = NETFLIX
    steps, batch = 8, 4096
    opt = RowOptimizer(name="sgd")
    params = jax.eval_shape(
        functools.partial(mf.init_params, num_users=m, num_items=n, k=K),
        jax.random.PRNGKey(0),
    )
    state = jax.eval_shape(functools.partial(mf.init_opt_state, opt=opt), params)
    place = lambda x: one_chip  # noqa: E731
    batches = {
        "user": _sds((steps, batch), jnp.int32, one_chip),
        "item": _sds((steps, batch), jnp.int32, one_chip),
        "rating": _sds((steps, batch), jnp.float32, one_chip),
    }
    scalar = _sds((), jnp.float32, one_chip)
    compiled = mf.train_epoch_scan.lower(
        _abstract(params, place), _abstract(state, place), batches,
        scalar, scalar, scalar, _sds((K,), jnp.float32, one_chip), None,
        opt=opt, lam=0.02, use_fused_kernel=True, interpret=False,
    ).compile()
    assert _has_kernel(compiled)


def test_train_epoch_scan_svdpp_compiles(one_chip, monkeypatch):
    """The SVD++ epoch program of ``bookx_svdpp_k128.svdpp_epochs``: k 128,
    histories of 32 slots, batches of 4,096, a 340,555-row implicit table
    whose row write takes 131,072 ids a step (512 KiB of ids in SMEM,
    half of it).  ``write_rows`` picks the kernel by backend, which is the
    CPU here: the test steers it to the chip's branch."""
    m, n, hist_len, steps, batch = 105_284, 340_554, 32, 8, 4096
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    opt = RowOptimizer(name="adagrad")
    params = jax.eval_shape(
        functools.partial(mf.init_params, num_users=m, num_items=n, k=K,
                          variant="svdpp"),
        jax.random.PRNGKey(0),
    )
    state = jax.eval_shape(functools.partial(mf.init_opt_state, opt=opt), params)
    place = lambda x: one_chip  # noqa: E731
    batches = {
        "user": _sds((steps, batch), jnp.int32, one_chip),
        "item": _sds((steps, batch), jnp.int32, one_chip),
        "rating": _sds((steps, batch), jnp.float32, one_chip),
    }
    scalar = _sds((), jnp.float32, one_chip)
    compiled = mf.train_epoch_scan.lower(
        _abstract(params, place), _abstract(state, place), batches,
        scalar, scalar, scalar, _sds((K,), jnp.float32, one_chip),
        _sds((m, hist_len), jnp.int32, one_chip),
        opt=opt, lam=0.02, interpret=False,
    ).compile()
    # the row-DMA kernel writes P, Q and Y; the bias columns go by scatter
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 3
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize(
    "shape,names", [((4,), ("model",)), ((2, 2), ("data", "model"))],
    ids=["model4", "data2xmodel2"],
)
def test_sharded_topk_kernel_compiles(topo, shape, names):
    """The engine's kernel-path ``topk_sharded`` program on a described
    4-chip mesh: one Pallas top-k per item slab, winners all-gathered over
    "model"."""
    from repro.distributed.sharding import serving_topk_kernel_specs
    from repro.serving import ServingEngine

    mesh = make_mesh(shape, names, devices=topo.devices)
    params = mf.init_params(jax.random.PRNGKey(0), 8, 64, K)
    engine = ServingEngine(params, 0.0, 0.0, use_kernel=True, interpret=False)
    n = 65_536
    in_specs, _ = serving_topk_kernel_specs(mesh)
    shapes = [((256, K), jnp.float32), ((), jnp.float32), ((n, K), jnp.float32),
              ((n, 1), jnp.int32), ((n, 1), jnp.float32)]
    args = [_sds(s, d, NamedSharding(mesh, spec))
            for (s, d), spec in zip(shapes, in_specs)]
    text = engine._sharded_program(mesh, 10, True).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "all-gather" in text


@pytest.mark.parametrize("grad_compression", ["none", "int8_ef"])
def test_train_step_shard_map_compiles_on_2x2(topo, grad_compression):
    """The owner-compute step partitioned over a described 2x2
    ("data", "model") host: user rows over data, item rows over model."""
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    m, n = NETFLIX[0] - NETFLIX[0] % 2, NETFLIX[1]
    batch = 8192
    opt = RowOptimizer(name="adagrad")
    params = jax.eval_shape(
        functools.partial(mf.init_params, num_users=m, num_items=n, k=K),
        jax.random.PRNGKey(0),
    )
    state = jax.eval_shape(functools.partial(mf.init_opt_state, opt=opt), params)
    if grad_compression == "int8_ef":
        state = jax.eval_shape(
            functools.partial(mf.init_error_feedback_state, mesh=mesh),
            params, state,
        )

    def rows(x):
        if x.shape[0] == m:
            return NamedSharding(mesh, P("data", *(None,) * (x.ndim - 1)))
        return NamedSharding(mesh, P("model", *(None,) * (x.ndim - 1)))

    batch_sh = NamedSharding(mesh, P("data"))
    batch_sds = {
        "user": _sds((batch,), jnp.int32, batch_sh),
        "item": _sds((batch,), jnp.int32, batch_sh),
        "rating": _sds((batch,), jnp.float32, batch_sh),
    }
    scalar = _sds((), jnp.float32, NamedSharding(mesh, P()))
    step = jax.jit(functools.partial(
        mf.train_step_shard_map, lr=0.05, lam=0.02, opt_name="adagrad",
        grad_compression=grad_compression, mesh=mesh,
    ))
    compiled = step.lower(
        _abstract(params, rows), _abstract(state, rows), batch_sds,
        scalar, scalar,
    ).compile()
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
