"""Parity tests for the §Perf shard_map formulations against their XLA
references, on a real 8-device SPMD mesh (subprocess: the device count must
be set before jax initializes)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


@pytest.mark.slow
def test_moe_shard_map_matches_xla_path():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.models.moe import (MoEConfig, init_moe_params,
                                      moe_ffn_xla, moe_ffn_shard_map)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = MoEConfig(num_experts=8, top_k=2, d_ff=32, num_shared=1,
                        capacity_factor=8.0)  # dropless => exact parity
        params = init_moe_params(jax.random.PRNGKey(0), 64, cfg,
                                 dtype=jnp.float32)
        x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (64, 64))
        ref, _ = moe_ffn_xla(x, params, cfg)
        with jax.sharding.set_mesh(mesh):
            got, _ = jax.jit(lambda x, p: moe_ffn_shard_map(
                x, p, cfg, mesh=mesh.abstract_mesh))(x, params)
        diff = float(jnp.max(jnp.abs(ref - got)))
        assert diff < 1e-5, diff

        def loss_sm(p, x):
            o, _ = moe_ffn_shard_map(x, p, cfg, mesh=mesh.abstract_mesh)
            return jnp.sum(o ** 2)
        def loss_ref(p, x):
            o, _ = moe_ffn_xla(x, p, cfg)
            return jnp.sum(o ** 2)
        with jax.sharding.set_mesh(mesh):
            g1 = jax.jit(jax.grad(loss_sm))(params, x)
        g2 = jax.grad(loss_ref)(params, x)
        for key in ("wg", "wi", "wo", "router"):
            d = float(jnp.max(jnp.abs(g1[key] - g2[key])))
            assert d < 1e-4, (key, d)
        print("MOE_PARITY_OK")
    """)
    assert "MOE_PARITY_OK" in out


@pytest.mark.slow
def test_mf_owner_compute_bit_exact():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mf
        from repro.launch.mesh import make_mesh
        from repro.optim.optimizers import RowOptimizer
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        m, n, k, B = 16, 8, 12, 16
        rng = np.random.default_rng(0)
        params = mf.init_params(jax.random.PRNGKey(0), m, n, k)
        for opt_name in ("adagrad", "sgd"):
            opt = RowOptimizer(name=opt_name)
            state = mf.init_opt_state(params, opt)
            users = np.concatenate(
                [rng.integers(s * 4, (s + 1) * 4, 4) for s in range(4)]
            ).astype(np.int32)  # ownership contract: shard s owns users [4s, 4s+4)
            batch = {
                "user": jnp.asarray(users),
                "item": jnp.asarray(rng.integers(0, n, B).astype(np.int32)),
                "rating": jnp.asarray(rng.uniform(1, 5, B).astype(np.float32)),
            }
            for t in (0.0, 0.05):
                ref_p, ref_s, _ = mf.train_step(
                    params, state, batch, jnp.float32(t), jnp.float32(t),
                    jnp.float32(0.05), jnp.ones((k,)), opt=opt, lam=0.02)
                with jax.sharding.set_mesh(mesh):
                    sm_p, sm_s, _ = jax.jit(
                        lambda p, s, b, tp, tq: mf.train_step_shard_map(
                            p, s, b, tp, tq, lr=0.05, lam=0.02,
                            opt_name=opt_name, mesh=mesh.abstract_mesh)
                    )(params, state, batch, jnp.float32(t), jnp.float32(t))
                assert float(jnp.max(jnp.abs(ref_p.p - sm_p.p))) < 2e-8
                assert float(jnp.max(jnp.abs(ref_p.q - sm_p.q))) < 2e-8
                if opt_name == "adagrad":
                    assert float(jnp.max(jnp.abs(
                        ref_s.q["acc"] - sm_s.q["acc"]))) < 2e-8
        print("MF_PARITY_OK")
    """)
    assert "MF_PARITY_OK" in out


def test_moe_shard_map_fallback_without_mesh():
    """Outside any mesh context the dispatcher must fall back to the XLA
    path (smoke-test environments)."""
    import jax
    import jax.numpy as jnp

    from repro.models.moe import MoEConfig, init_moe_params, moe_ffn

    cfg = MoEConfig(num_experts=4, top_k=2, d_ff=16, capacity_factor=8.0)
    params = init_moe_params(jax.random.PRNGKey(0), 32, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    out, aux = moe_ffn(x, params, cfg, use_shard_map=True)  # no ambient mesh
    assert out.shape == x.shape
    assert bool(jnp.isfinite(aux))


@pytest.mark.slow
def test_int8_error_feedback_tracks_fp32():
    """Multi-epoch training with int8-compressed gradient collectives:
    error feedback (per-sender quantization residuals folded into the next
    transmission) must keep the final-epoch training error within a tight
    tolerance of the fp32 exchange — and at least as close as plain int8
    without feedback."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mf
        from repro.launch.mesh import make_mesh
        from repro.optim.optimizers import RowOptimizer

        mesh = make_mesh((2, 2), ("data", "model"))
        m, n, k, B, steps, epochs = 16, 8, 12, 16, 40, 3
        rng = np.random.default_rng(0)
        params0 = mf.init_params(jax.random.PRNGKey(0), m, n, k)
        opt = RowOptimizer(name="adagrad")
        # ownership contract: rating b on data shard s => user in [8s, 8s+8)
        users = np.stack([
            np.concatenate([rng.integers(s * 8, (s + 1) * 8, B // 2)
                            for s in range(2)])
            for _ in range(steps)
        ]).astype(np.int32)
        batches = {
            "user": jnp.asarray(users),
            "item": jnp.asarray(
                rng.integers(0, n, (steps, B)).astype(np.int32)),
            "rating": jnp.asarray(
                rng.uniform(1, 5, (steps, B)).astype(np.float32)),
        }

        def final_err(gc):
            state = mf.init_opt_state(params0, opt)
            if gc == "int8_ef":
                with jax.sharding.set_mesh(mesh):
                    state = mf.init_error_feedback_state(
                        params0, state, mesh)
            params = params0
            with jax.sharding.set_mesh(mesh):
                for _ in range(epochs):
                    params, state, metrics = mf.train_epoch_scan_shard_map(
                        params, state, batches, 0.0, 0.0, lr=0.05,
                        lam=0.02, opt_name="adagrad", grad_compression=gc,
                        mesh=mesh.abstract_mesh)
            return float(metrics["abs_err"])

        fp32 = final_err("none")
        int8 = final_err("int8")
        ef = final_err("int8_ef")
        gap_int8 = abs(int8 - fp32) / fp32
        gap_ef = abs(ef - fp32) / fp32
        print("fp32", fp32, "int8", int8, "ef", ef)
        # residual accumulation: the EF run must stay within 1% of the
        # full-precision trajectory, and never meaningfully worse than
        # feedback-free int8 (both gaps are O(1e-4) at this scale, so the
        # comparison gets noise-level slack rather than strict ordering)
        assert gap_ef < 0.01, (gap_ef, ef, fp32)
        assert gap_ef <= gap_int8 + 5e-4, (gap_ef, gap_int8)
        print("INT8_EF_OK")
    """)
    assert "INT8_EF_OK" in out


@pytest.mark.slow
def test_int8_error_feedback_stable_on_skewed_items():
    """A Zipf-like item stream puts the same item row in a batch many
    times over.  Each row's quantization residual must ride on one
    occurrence only: folded into all D duplicates it is sent D times but
    banked once, grows by (1 - D) per step and blows up to NaN."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mf
        from repro.launch.mesh import make_mesh
        from repro.optim.optimizers import RowOptimizer

        mesh = make_mesh((2, 2), ("data", "model"))
        m, n, k, B, steps = 32, 64, 16, 64, 30
        rng = np.random.default_rng(1)
        users = np.stack([
            np.concatenate([rng.integers(s * 16, (s + 1) * 16, B // 2)
                            for s in range(2)])
            for _ in range(steps)
        ]).astype(np.int32)
        # half of every batch rates item 0, the rest a geometric tail
        items = np.where(rng.random((steps, B)) < 0.5, 0,
                         np.minimum(rng.geometric(0.2, (steps, B)), n - 1))
        batches = {
            "user": jnp.asarray(users),
            "item": jnp.asarray(items.astype(np.int32)),
            "rating": jnp.asarray(
                rng.uniform(0, 10, (steps, B)).astype(np.float32)),
        }
        params0 = mf.init_params(jax.random.PRNGKey(0), m, n, k)
        opt = RowOptimizer(name="adagrad")

        def final(gc):
            params = jax.tree.map(jnp.copy, params0)
            state = mf.init_opt_state(params, opt)
            with jax.sharding.set_mesh(mesh):
                if gc == "int8_ef":
                    state = mf.init_error_feedback_state(params, state, mesh)
                for _ in range(2):
                    params, state, metrics = mf.train_epoch_scan_shard_map(
                        params, state, batches, 0.0, 0.0, lr=0.05, lam=0.02,
                        opt_name="adagrad", grad_compression=gc, mesh=mesh)
            return params, state, float(metrics["abs_err"])

        _, _, fp32 = final("none")
        params, state, ef = final("int8_ef")
        print("fp32", fp32, "ef", ef)
        assert np.isfinite(ef), ef
        assert bool(jnp.isfinite(params.q).all() & jnp.isfinite(params.p).all())
        assert bool(jnp.isfinite(state.q["ef_gather"]).all())
        assert abs(ef - fp32) / fp32 < 0.01, (ef, fp32)
        print("INT8_EF_SKEW_OK")
    """)
    assert "INT8_EF_SKEW_OK" in out
