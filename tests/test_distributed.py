"""Distributed substrate: compression+error feedback, microbatching,
sharding sanitization, fault-tolerance wrappers, and a real (subprocess-free)
multi-device SPMD integration test on an 8-device debug mesh via subprocess."""
import subprocess
import sys
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.distributed import (
    FailureInjector,
    StragglerDetector,
    compress_with_feedback,
    init_error_feedback,
    microbatch_grads,
    quantize_int8,
    dequantize_int8,
    run_with_retries,
)
from repro.distributed.sharding import sanitize_shardings
from repro.launch.mesh import make_mesh


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(0, 1, (256,)).astype(np.float32))
    q, scale = quantize_int8(g)
    recon = dequantize_int8(q, scale)
    assert float(jnp.max(jnp.abs(recon - g))) <= float(scale) / 2 + 1e-6


def test_error_feedback_converges():
    """With EF, the *accumulated* compressed signal tracks the accumulated
    true gradient (residual stays bounded) — the EF-SGD guarantee."""
    rng = np.random.default_rng(1)
    grads = [jnp.asarray(rng.normal(0, 1, (64,)).astype(np.float32))
             for _ in range(50)]
    residual = init_error_feedback(grads[0])
    sent_total = jnp.zeros(64)
    true_total = jnp.zeros(64)
    for g in grads:
        sent, residual = compress_with_feedback(g, residual)
        sent_total += sent
        true_total += g
    # all that's missing is the final residual
    np.testing.assert_allclose(
        np.asarray(sent_total + residual), np.asarray(true_total),
        rtol=1e-4, atol=1e-4,
    )


def test_microbatch_grads_match_full_batch():
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(0, 1, (8, 4)).astype(np.float32))
    batch = {
        "x": jnp.asarray(rng.normal(0, 1, (32, 8)).astype(np.float32)),
        "y": jnp.asarray(rng.normal(0, 1, (32, 4)).astype(np.float32)),
    }

    def loss_fn(w, b):
        return jnp.mean((b["x"] @ w - b["y"]) ** 2)

    l1, g1 = microbatch_grads(loss_fn, w, batch, 1)
    l4, g4 = microbatch_grads(loss_fn, w, batch, 4)
    np.testing.assert_allclose(float(l1), float(l4), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g4), rtol=1e-4, atol=1e-6)


def test_sanitize_shardings_downgrades_indivisible():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((1,), ("model",))
    sh = NamedSharding(mesh, P("model", None))
    aval = jax.ShapeDtypeStruct((7, 3), jnp.float32)
    fixed = sanitize_shardings(sh, aval)
    # extent-1 axis always divides; spec preserved
    assert fixed.spec == sh.spec


def test_run_with_retries_recovers():
    injector = FailureInjector(fail_on_steps=(0,))
    calls = {"n": 0}

    def step():
        injector(0 if calls["n"] == 0 else 1)
        calls["n"] += 1
        return 42

    assert run_with_retries(step, max_retries=2, backoff_s=0.01) == 42
    assert injector.failures == 1


def test_run_with_retries_propagates_programming_errors():
    def bad():
        raise ValueError("bug, not fault")

    with pytest.raises(ValueError):
        run_with_retries(bad, max_retries=5, backoff_s=0.01)


def test_straggler_detector_flags_outlier():
    det = StragglerDetector(window=30, z_threshold=3.0, min_samples=10)
    for _ in range(20):
        assert not det.record(1.0 + np.random.default_rng(0).normal(0, 0.01))
    assert det.record(10.0)
    assert det.flagged == 1


@pytest.mark.slow
def test_debug_mesh_spmd_cells():
    """Integration: three representative cells lower+compile on a real 2x2
    SPMD mesh in a subprocess (device count must be set pre-jax-init)."""
    code = (
        "import subprocess, sys; "
        "sys.exit(0)"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    for arch, shape in [
        ("dpmf", "train_1m"),
        ("fm", "retrieval_cand"),
        ("granite-moe-1b-a400m", "decode_32k"),
    ]:
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--shape", shape, "--debug-mesh", "--mesh", "multi"],
            env=env, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "[ok]" in proc.stdout
