"""The trace reducer: on hand-made planes whose busy time, idle gaps and
labels are counted by hand, and on a small trace recorded on a v5e."""
import os
import types

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")
MS = 1_000_000   # ns


def _ev(name, start_ms, dur_ms):
    return types.SimpleNamespace(name=name, start_ns=int(start_ms * MS),
                                 duration_ns=int(dur_ms * MS))


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def _planes():
    host = _plane("/host:CPU", {
        "main": [_ev("bench.window", 0, 100), _ev("bench.call", 10, 50),
                 _ev("bench.call", 70, 25), _ev("dispatch", 12, 3)],
        "worker": [_ev("host_sync", 72, 6)],
    })
    # core 0: ops 10-40 (two overlapping), 50-60, and 90-110 clipped at 100
    tpu0 = _plane("/device:TPU:0", {
        "XLA Modules": [_ev("jit_step(12)", 10, 30), _ev("jit_other(3)", 50, 10),
                        _ev("jit_step(12)", 90, 20)],
        "XLA Ops": [_ev("fusion.1", 10, 20), _ev("fusion.2", 25, 15),
                    _ev("custom-call", 50, 10), _ev("fusion.1", 90, 20)],
    })
    # core 1: one op 0-50
    tpu1 = _plane("/device:TPU:1", {"XLA Ops": [_ev("fusion.9", 0, 50)]})
    return [host, tpu0, tpu1]


def test_busy_and_window_by_hand():
    r = trace.reduce(_planes())
    assert r["window_s"] == pytest.approx(0.100)
    # core 0 busy 30 + 10 + 10 = 50 ms, core 1 50 ms
    assert r["busy_s"] == pytest.approx(0.050)


def test_program_and_op_times_by_hand():
    r = trace.reduce(_planes())
    assert trace.module_seconds(r, "jit_step") == pytest.approx(0.040)
    # the second fusion.1 runs 90-110 and counts up to the window's end
    assert trace.op_stats(r, "fusion.1") == (2, pytest.approx(0.030))
    assert r["device_ops"][0] == ["fusion.9", pytest.approx(0.050)]


def test_self_time_leaves_out_nested_operations():
    tpu = _plane("/device:TPU:0", {"XLA Ops": [
        _ev("while", 10, 50), _ev("body.a", 15, 10), _ev("body.b", 30, 20),
        _ev("inner", 35, 5)]})
    r = trace.reduce([_planes()[0], tpu])
    # while 50 - 10 - 20 = 20; body.b 20 - 5 = 15
    assert dict(r["device_ops"]) == {"while": pytest.approx(0.020),
                                     "body.b": pytest.approx(0.015),
                                     "body.a": pytest.approx(0.010),
                                     "inner": pytest.approx(0.005)}
    assert r["busy_s"] == pytest.approx(0.050)


def test_idle_gaps_labelled_by_host_spans():
    gaps = dict(trace.reduce(_planes())["idle_gaps"])
    # core 0 idle: 0-10 (middle 5: no span but the window), 40-50 (middle
    # 45: the first bench.call), 60-90 (middle 75: the second bench.call,
    # and host_sync on another thread)
    assert gaps == {"no bench span": pytest.approx(0.010),
                    "bench.call": pytest.approx(0.010),
                    "bench.call > host_sync": pytest.approx(0.030)}


def test_window_span_is_required():
    planes = _planes()
    planes[0].lines[0].events = planes[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(planes)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_v5e_trace():
    r = trace.reduce(list(trace.load(DATA)))
    assert 0.1 < r["window_s"] < 10
    assert 0 < r["busy_s"] < r["window_s"] - 0.09   # the 100 ms sleep is idle
    gaps = dict(r["idle_gaps"])
    idle = sum(v for k, v in gaps.items() if k.startswith("bench.idle"))
    assert 0.095 < idle < 0.2
    assert trace.module_seconds(r, "train_epoch_scan") > 0
    count, seconds = trace.op_stats(r, trace.TOPK_KERNEL)
    assert count >= 1 and seconds > 0
