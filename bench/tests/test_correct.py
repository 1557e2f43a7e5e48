"""``correct`` on the CPU at a small size: sound runs pass each cell's
limits; the lower-precision control and each fault the cell can have fail
them.  The drivers run as on the chip, with the timed path broken
underneath where a fault is planted."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import checks, reference, run
from bench.harness import Run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2**33 + 21


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def _cell(name, **traffic_overrides):
    """A cell's configuration, traffic and limits by their file names (the
    online cell is not in BENCHMARK.json yet), at a small size."""
    config, mix = name.split(".")
    cell = {"limits": _load("limits", name + ".json")}
    cfg = dict(_load("configs", config + ".json"), num_users=300, num_items=700,
               num_ratings=30000, k=16, batch_size=512, epochs=5)
    traffic = dict(_load("traffic", mix + ".json"), **traffic_overrides)
    return cell, cfg, traffic


def _correct(cell, cfg, traffic, seconds=0.5):
    peak = _load("peaks.json")["TPU v5 lite"]
    driver = run.load_driver(traffic["kind"])
    driver.check(cfg, traffic)
    out = driver.drive(Run(time.perf_counter(), peak, 1, None), cfg, traffic, SEED, seconds)
    result = out.checks(cell["limits"])
    return all(c["value"] <= c["limit"] for c in result.values()), result


TRAIN_CELLS = ["bookx_k128.train_job", "ml25m_k128.train_epochs"]
SERVE_CELLS = [("bookx_k128.batch_top100", {"users_per_call": 64, "sample_users": 32}),
               ("ml25m_k128.online_top10", {"rate_per_s": 200, "sample_requests": 64})]


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_sound_training_is_correct(name):
    ok, result = _correct(*_cell(name))
    assert ok, result


@pytest.mark.parametrize("name,overrides", SERVE_CELLS)
def test_sound_serving_is_correct(name, overrides):
    ok, result = _correct(*_cell(name, **overrides))
    assert ok, result


def _unchanged(orig):
    def step(params, opt_state, batches, *args, **kwargs):
        copy = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
        _, _, metrics = orig(*copy, batches, *args, **kwargs)
        return params, opt_state, metrics
    return step


def _half_batch(orig):
    def step(params, opt_state, batches, *args, **kwargs):
        half = {k: v[:, : v.shape[1] // 2] for k, v in batches.items()}
        return orig(params, opt_state, half, *args, **kwargs)
    return step


@pytest.mark.parametrize("name", TRAIN_CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_training_faults_are_not_correct(name, fault, monkeypatch):
    from repro.core import mf

    monkeypatch.setattr(mf, "train_epoch_scan", fault(mf.train_epoch_scan))
    ok, result = _correct(*_cell(name))
    assert not ok, result


def _altered(orig):
    def topk(self, users, k=10):
        s, i = orig(self, users, k)
        return s, (i + 1) % self.n_items
    return topk


def _half_users(orig):
    def topk(self, users, k=10):
        users = np.asarray(users)
        half = max(len(users) // 2, 1)
        s, i = orig(self, users[:half], k)
        pick = np.arange(len(users)) % half
        return s[pick], i[pick]
    return topk


@pytest.mark.parametrize("name,overrides", SERVE_CELLS)
@pytest.mark.parametrize("fault", [_altered, _half_users])
def test_serving_faults_are_not_correct(name, overrides, fault, monkeypatch):
    from repro.serving.engine import ServingEngine

    monkeypatch.setattr(ServingEngine, "topk", fault(ServingEngine.topk))
    ok, result = _correct(*_cell(name, **overrides))
    assert not ok, result


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_training_control_is_not_correct(name):
    """The reference in bfloat16 in the program's place."""
    cell, cfg, _ = _cell(name)
    train, _ = __import__("bench.data", fromlist=["ratings"]).ratings(cfg, 5)
    ref = reference.train_readings(cfg, train, 9)
    control = reference.train_readings(cfg, train, 9, dtype=jnp.bfloat16)
    result = checks.train_gaps(control, ref)
    assert any(result[k] > cell["limits"][k] for k in result), result


@pytest.mark.parametrize("name,overrides", SERVE_CELLS)
def test_serving_control_is_not_correct(name, overrides):
    """The reference at Precision.HIGH's three bfloat16 passes in the
    program's place, for every user."""
    from bench import data

    cell, cfg, traffic = _cell(name, **overrides)
    p, q = data.factor_tables(cfg, 5)
    tables = (p, q, reference.table_threshold(p, cfg["pruning_rate"]),
              reference.table_threshold(q, cfg["pruning_rate"]))
    users = np.arange(cfg["num_users"], dtype=np.int32)
    first = np.zeros((len(users), traffic["topk"]), np.int32)
    _, _, _, top_s, top_i = checks.reference_blocks(
        tables, users, first, topk=traffic["topk"], precision="bf16_3x")
    result = checks.topk_gaps(tables, users, top_s, top_i, topk=traffic["topk"])
    assert any(result[k] > cell["limits"][k] for k in result), result
