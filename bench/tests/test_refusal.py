"""A run refuses, with no result line, what it cannot measure."""
import os
import subprocess
import sys
import types

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fake_jax(platform, kind, count):
    device = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [device] * count)


def test_cpu_run_exits_nonzero_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bookx_k128.batch_top100",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


@pytest.mark.parametrize("platform,kind,count,chips,why", [
    ("cpu", "cpu", 1, 1, "not a TPU"),
    ("gpu", "NVIDIA H100", 1, 1, "not a TPU"),
    ("tpu", "TPU v9 imaginary", 1, 1, "not in bench/peaks.json"),
    ("tpu", "TPU v5 lite", 1, 4, "asks for 4 chips"),
])
def test_device_refusals(platform, kind, count, chips, why):
    with pytest.raises(run.Refused, match=why):
        run.device_info(_fake_jax(platform, kind, count), chips)


def test_known_device_is_accepted():
    info = run.device_info(_fake_jax("tpu", "TPU v5 lite", 4), 4)
    assert info["count"] == 4 and info["peak"]["bf16_flops_per_s"] == 197e12


def test_unknown_workload_is_refused():
    with pytest.raises(run.Refused, match="unknown workload"):
        run.load_cell("no_such.cell")


def test_every_cell_loads_with_its_files():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for cell in spec["workloads"]:
        loaded = run.load_cell(cell["name"])
        driver = run.load_driver(loaded["traffic"]["kind"])
        driver.check(loaded["config"], loaded["traffic"])
        assert loaded["limits"]
        for metric in loaded["per_layer"]:
            assert callable(run.load_reader(metric["name"]))
        assert {m["name"] for m in loaded["end_to_end"]} >= {"setup_s"}


def _train_cfg(**overrides):
    import json

    with open(os.path.join(ROOT, "bench", "configs", "ml25m_k128.json")) as f:
        return dict(json.load(f), **overrides)


@pytest.mark.parametrize("key,value", [
    ("optimizer", "sgd"), ("variant", "bias"), ("objective", "bpr"),
    ("rearrange", False), ("checkpoint_dir", "ckpt"),
])
def test_training_refuses_what_the_reference_does_not_implement(key, value):
    from bench import training

    with pytest.raises(run.Refused, match=key):
        training.train_config(_train_cfg(**{key: value}), 0)


def test_training_passes_every_trainer_key_through():
    from bench import training

    tcfg = training.train_config(_train_cfg(use_fused_kernel=True, eval_batch_size=512), 7)
    assert tcfg.use_fused_kernel is True and tcfg.eval_batch_size == 512
    assert tcfg.seed == 7 and tcfg.k == 128 and tcfg.pruning_rate == 0.3


def test_unknown_traffic_kind_is_refused():
    with pytest.raises(run.Refused, match="bench/drivers/no_such_kind.py"):
        run.load_driver("no_such_kind")
