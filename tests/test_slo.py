"""SLO-aware adaptive pruning controller (ISSUE-7 tentpole).

Pins the control law (degrade on p99/depth/expiry, AIMD relax, quality
guardrail override), the per-priority-class rate schedule and its clamps,
and the application fan-out: primary engine swap, publisher serving-
threshold pin (a snapshot publish must not revert a degradation), and the
rolling per-replica fleet rollout.
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import mf
from repro.core.threshold import (
    empirical_pruned_fraction,
    measure_stats,
    threshold_for_rate,
)
from repro.online import EventBatch, OnlineUpdater, SnapshotPublisher
from repro.serving import (
    LatencyWindow,
    ServingEngine,
    SLOConfig,
    SLOController,
)
from repro.serving.fleet import ServingFleet, make_message


def _params(m=30, n=240, k=16, seed=0):
    return mf.init_params(
        jax.random.PRNGKey(seed), m, n, k, variant="bias", global_mean=3.5,
    )


def _slow_window(n=32, latency_s=0.120, capacity=64, priority=0):
    win = LatencyWindow(capacity)
    for _ in range(n):
        win.record(latency_s, priority=priority)
    return win


def _config(**kw):
    base = dict(p99_budget_ms=50.0, min_window=8, tick_interval_s=0.0)
    base.update(kw)
    return SLOConfig(**base)


# ---------------------------------------------------------------------------
# LatencyWindow
# ---------------------------------------------------------------------------


def test_latency_window_percentiles_and_count():
    win = LatencyWindow(8)
    assert np.isnan(win.percentile(99))
    for ms in (1, 2, 3, 4):
        win.record(ms / 1e3)
    assert win.count == 4
    assert win.percentile(50) == pytest.approx(2.5e-3)
    # ring: 12 records into capacity 8 keeps the last 8, count stays total
    for ms in range(5, 13):
        win.record(ms / 1e3)
    assert win.count == 12
    lat, _ = win.snapshot()
    assert lat.size == 8
    assert win.percentile(0) == pytest.approx(5e-3)


def test_latency_window_priority_filter():
    win = LatencyWindow(16)
    for _ in range(4):
        win.record(0.001, priority=0)
        win.record(0.100, priority=5)
    assert win.percentile(99, priority=0) < 0.01
    assert win.percentile(99, priority=5) > 0.05
    assert np.isnan(win.percentile(99, priority=3))


def test_latency_window_rejects_bad_capacity():
    with pytest.raises(ValueError):
        LatencyWindow(0)


# ---------------------------------------------------------------------------
# control law
# ---------------------------------------------------------------------------


def test_tick_degrades_on_p99_breach():
    params = _params()
    ctl = SLOController(
        config=_config(),
        window=_slow_window(),
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    d = ctl.tick()
    assert d.action == "degrade"
    assert d.p99_ms > 50.0
    assert ctl.base_rate == pytest.approx(ctl.config.step_up)
    assert d.swapped and d.t_q > 0.0 and d.t_p > 0.0


def test_tick_degrades_on_depth_watermark_alone():
    params = _params()
    win = LatencyWindow(16)  # empty: no latency signal at all
    ctl = SLOController(
        config=_config(depth_high=10),
        window=win,
        depth_fn=lambda: 50,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    assert ctl.tick().action == "degrade"


def test_tick_degrades_on_expiry():
    params = _params()
    expired = {"n": 0}
    ctl = SLOController(
        config=_config(),
        window=LatencyWindow(16),
        depth_fn=lambda: 0,
        expired_fn=lambda: expired["n"],
        params_fn=lambda: params,
    )
    expired["n"] = 3
    assert ctl.tick().action == "degrade"
    # expirations are counted per tick, not cumulatively
    d2 = ctl.tick()
    assert d2.expired == 0 and d2.action == "hold"


def test_tick_relaxes_when_comfortable():
    params = _params()
    win = _slow_window(capacity=32, n=32)
    ctl = SLOController(
        config=_config(),
        window=win,
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    assert ctl.tick().action == "degrade"
    # flush the ring with fast completions: comfortably under budget now
    for _ in range(32):
        win.record(0.001)
    d = ctl.tick()
    assert d.action == "relax"
    assert ctl.base_rate == pytest.approx(
        ctl.config.step_up - ctl.config.step_down
    )


def test_relax_stops_at_measured_trained_floor():
    params = _params()
    rate = 0.3
    t_q = float(threshold_for_rate(measure_stats(params.q), rate))
    engine = ServingEngine(params, t_q, t_q)
    win = LatencyWindow(32)
    for _ in range(32):
        win.record(0.001)
    ctl = SLOController(
        engine,
        config=_config(),
        window=win,
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
    )
    measured = float(empirical_pruned_fraction(params.q, t_q))
    assert ctl.floor_rate == pytest.approx(measured)
    assert measured > 0.2  # the solve actually landed near the asked rate
    for _ in range(5):
        ctl.tick()
    assert ctl.base_rate == pytest.approx(ctl.floor_rate)
    engine.stop()


def test_degrade_clamps_at_max_rate():
    params = _params()
    ctl = SLOController(
        config=_config(max_rate=0.5, depth_high=1),
        window=LatencyWindow(16),
        depth_fn=lambda: 100,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    for _ in range(10):
        ctl.tick()
    assert ctl.base_rate == pytest.approx(0.5)
    assert ctl.degrades == 10


def test_quality_pressure_relaxes_despite_overload():
    params = _params()
    ctl = SLOController(
        config=_config(depth_high=1),
        window=_slow_window(),
        depth_fn=lambda: 100,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    assert ctl.tick().action == "degrade"
    hook = ctl.quality_hook()
    assert hook.controller is ctl
    hook(SimpleNamespace(
        events=200, window_events=50, window_mae=1.0, window_rmse=1.2,
        mae=0.6, rmse=0.8, ema_mae=0.5, ema_rmse=0.7,
    ))
    d = ctl.tick()  # still overloaded — quality wins anyway
    assert d.action == "quality_relax"
    assert ctl.quality_relaxes == 1
    # pressure is one-shot: next tick degrades again under the same load
    assert ctl.tick().action == "degrade"


def test_quality_pressure_needs_real_drift():
    params = _params()
    ctl = SLOController(
        config=_config(),
        window=LatencyWindow(16),
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    # too few events / window error within bound: no pressure
    ctl.note_quality(SimpleNamespace(
        events=10, window_events=10, window_mae=9.0, window_rmse=9.0,
        mae=1.0, rmse=1.0, ema_mae=0.5, ema_rmse=0.5,
    ))
    assert not ctl._quality_pressure
    ctl.note_quality(SimpleNamespace(
        events=200, window_events=50, window_mae=0.55, window_rmse=0.7,
        mae=0.5, rmse=0.6, ema_mae=0.5, ema_rmse=0.6,
    ))
    assert not ctl._quality_pressure


def test_effective_rates_per_class_and_clamps():
    params = _params()
    ctl = SLOController(
        config=_config(max_rate=0.6, background_offset=0.2,
                       class_offsets={7: 0.05}),
        window=LatencyWindow(16),
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    ctl.base_rate = 0.5
    rates = ctl.effective_rates((0, 3, 7))
    assert rates[0] == pytest.approx(0.5)
    assert rates[3] == pytest.approx(0.6)   # 0.5 + 0.2 clamped to max
    assert rates[7] == pytest.approx(0.55)  # explicit per-class offset
    # background is never served less pruned than interactive
    assert rates[3] >= rates[0] and rates[7] >= rates[0]


def test_applied_threshold_follows_most_latency_sensitive_class():
    params = _params()
    # only background traffic in the window: serve at the background rate
    win = _slow_window(n=32, priority=5)
    ctl = SLOController(
        config=_config(background_offset=0.2),
        window=win,
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    d = ctl.tick()
    assert d.applied_class == 5
    assert d.applied_rate == pytest.approx(d.rates[5])
    # interactive traffic shows up: the applied threshold must follow the
    # most latency-sensitive class, not the background one
    for _ in range(16):
        win.record(0.120, priority=0)
    d2 = ctl.tick()
    assert d2.applied_class == 0
    assert d2.applied_rate == pytest.approx(d2.rates[0])
    assert d2.rates[5] >= d2.rates[0]


def test_small_rate_moves_skip_the_swap():
    params = _params()
    ctl = SLOController(
        config=_config(depth_high=1, step_up=0.001, rate_eps=0.01),
        window=LatencyWindow(16),
        depth_fn=lambda: 100,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    first = ctl.tick()
    assert first.swapped  # first apply always lands
    moves = [ctl.tick().swapped for _ in range(5)]
    assert not any(moves)  # 0.001 steps stay under rate_eps
    assert ctl.swaps == 1


def test_maybe_tick_rate_limits():
    params = _params()
    ctl = SLOController(
        config=_config(tick_interval_s=30.0),
        window=LatencyWindow(16),
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    assert ctl.maybe_tick() is not None
    assert ctl.maybe_tick() is None  # 30s have not elapsed
    assert ctl.ticks == 1


# ---------------------------------------------------------------------------
# the quality frontier the controller walks
# ---------------------------------------------------------------------------


def test_pruning_harder_never_raises_ndcg():
    """Along the rate ladder the controller degrades through, top-10 NDCG
    against the dense ranking never rises, and rate 0 is exactly the dense
    ranking.  The factors have a decaying latent spectrum, as trained
    factors do, so pruning evicts the low-energy tail first."""
    m, n, k, topk = 64, 500, 16, 10
    base = mf.init_params(jax.random.PRNGKey(0), m, n, k)
    scale = jnp.asarray(0.97 ** np.arange(k), jnp.float32)[None, :]
    params = base._replace(p=base.p * scale, q=base.q * scale)
    users = np.random.default_rng(0).integers(0, m, 32)
    scores = np.asarray(params.p[users] @ params.q.T)
    dense_idx = np.argsort(-scores, axis=1, kind="stable")[:, :topk]
    discounts = 1.0 / np.log2(np.arange(2, topk + 2))
    sp, sq = measure_stats(params.p), measure_stats(params.q)
    ndcgs = []
    for rate in (0.0, 0.12, 0.35):
        engine = ServingEngine(params, threshold_for_rate(sp, rate),
                               threshold_for_rate(sq, rate),
                               use_kernel=False, max_batch=32)
        _, idx = engine.topk(users, topk)
        idx = np.asarray(idx)
        if rate == 0.0:
            np.testing.assert_array_equal(idx, dense_idx)
        hits = np.stack([np.isin(a, b) for a, b in zip(idx, dense_idx)])
        ndcgs.append(float(np.mean(hits @ discounts) / discounts.sum()))
    assert ndcgs[0] == 1.0
    assert all(hi <= lo + 1e-9 for lo, hi in zip(ndcgs, ndcgs[1:])), ndcgs
    assert ndcgs[-1] < 1.0, ndcgs


# ---------------------------------------------------------------------------
# application fan-out
# ---------------------------------------------------------------------------


def test_controller_applies_thresholds_to_engine():
    params = _params()
    engine = ServingEngine(params, 0.0, 0.0)
    dense_s, dense_i = engine.topk(np.arange(8), 5)
    ctl = SLOController(
        engine,
        config=_config(),
        window=_slow_window(),
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
    )
    d = ctl.tick()
    assert float(engine.t_q) == pytest.approx(d.t_q)
    assert float(engine.t_q) > 0.0
    s, i = engine.topk(np.arange(8), 5)  # pruned serving still works
    assert i.shape == dense_i.shape
    engine.stop()


def test_publisher_pin_survives_snapshot_publish():
    rng = np.random.default_rng(0)
    params = _params()
    engine = ServingEngine(params, 0.0, 0.0)
    upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=0)
    pub = SnapshotPublisher(engine, upd)
    ctl = SLOController(
        engine,
        config=_config(),
        window=_slow_window(),
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
        publisher=pub,
    )
    d = ctl.tick()
    assert float(engine.t_q) == pytest.approx(d.t_q) and d.t_q > 0.0
    # a publish swaps new params in but must keep the SLO thresholds
    upd.apply(EventBatch(
        user=rng.integers(0, 30, 24).astype(np.int32),
        item=rng.integers(0, 240, 24).astype(np.int32),
        rating=rng.uniform(1, 5, 24).astype(np.float32),
    ))
    pub.publish()
    assert float(engine.t_q) == pytest.approx(d.t_q)
    assert float(engine.t_p) == pytest.approx(d.t_p)
    # unpinning reverts the NEXT publish to the model thresholds
    pub.clear_serving_thresholds()
    upd.apply(EventBatch(
        user=rng.integers(0, 30, 24).astype(np.int32),
        item=rng.integers(0, 240, 24).astype(np.int32),
        rating=rng.uniform(1, 5, 24).astype(np.float32),
    ))
    pub.publish()
    assert float(engine.t_q) == pytest.approx(float(upd.t_q))
    engine.stop()


def test_fleet_rolling_threshold_rollout():
    params = _params()
    fleet = ServingFleet(params, 0.0, 0.0, replicas=2, backend="local")
    try:
        ctl = SLOController(
            config=_config(),
            window=_slow_window(),
            depth_fn=lambda: 0,
            expired_fn=lambda: 0,
            router=fleet.router,
        )
        d = ctl.tick()
        assert d.t_q > 0.0
        for rep in fleet.replicas:
            assert float(rep.engine.t_q) == pytest.approx(d.t_q)
        # replicated snapshots must NOT revert the pinned thresholds
        upd = OnlineUpdater(params, None, 0.0, 0.0, batch_size=32, seed=1)
        rng = np.random.default_rng(1)
        upd.apply(EventBatch(
            user=rng.integers(0, 30, 24).astype(np.int32),
            item=rng.integers(0, 240, 24).astype(np.int32),
            rating=rng.uniform(1, 5, 24).astype(np.float32),
        ))
        msg = make_message(upd.snapshot(), 1, 0, full=False)
        fleet.apply_update(msg)
        for rep in fleet.replicas:
            assert rep.version == 1
            assert float(rep.engine.t_q) == pytest.approx(d.t_q)
    finally:
        fleet.close()


def test_queue_latency_feeds_the_controller():
    params = _params()
    engine = ServingEngine(params, 0.0, 0.0)
    queue = engine.start()
    try:
        futs = [engine.submit(u, 5) for u in range(8)]
        for f in futs:
            f.result(timeout=60)
        assert queue.latency.count >= 8
        ctl = SLOController(
            engine,
            queue=queue,
            config=_config(min_window=4, p99_budget_ms=1e9),
        )
        d = ctl.tick()
        assert d.completed >= 8
        assert np.isfinite(d.p99_ms)
        assert d.action in ("hold", "relax")
    finally:
        engine.stop()


def test_report_shape():
    params = _params()
    ctl = SLOController(
        config=_config(),
        window=_slow_window(),
        depth_fn=lambda: 0,
        expired_fn=lambda: 0,
        params_fn=lambda: params,
    )
    ctl.tick()
    rep = ctl.report()
    assert rep["ticks"] == 1 and rep["degrades"] == 1
    assert rep["applied_t_q"] > 0.0
    assert rep["last_decision"]["action"] == "degrade"
    assert isinstance(rep["rates"], dict)
    import json
    json.dumps(rep)  # report must be JSON-serializable as-is
