"""Device idle attributed to the program's ``repro.*`` spans: on hand-made
planes whose holes and spans are counted by hand, and on the small trace
recorded on a v5e, which holds no program spans."""
import os
import shutil
import types

import pytest

from bench import program_spans, trace
from bench.tests.test_trace import DATA, _ev, _plane


def _serving_planes():
    host = _plane("/host:CPU", {
        "main": [
            _ev("bench.window", 0, 100),
            _ev("bench.topk_call", 4, 92),              # not the program's
            _ev("repro.serving.launch", -20, 22),       # starts before the window
            _ev("repro.serving.topk", 5, 90),
            _ev("repro.serving.launch", 20, 10),
            _ev("repro.serving.gather", 42, 8),         # nested in topk
            _ev("repro.serving.fetch", 55, 10),         # its sibling
            _ev("np.asarray", 56, 4),                   # runtime event, ignored
            _ev("repro.serving.launch", 98, 12),        # runs past the window
        ],
    })
    # busy 1-40, 70-85 (two overlapping ops); idle 0-1, 40-70, 85-100
    tpu = _plane("/device:TPU:0", {"XLA Ops": [
        _ev("fusion.1", 1, 39), _ev("fusion.2", 70, 10), _ev("fusion.3", 78, 7)]})
    return [host, tpu]


def test_hole_split_by_innermost_span():
    r = program_spans.attribute(_serving_planes())
    ms = {k: v * 1e3 for k, v in r["idle_s"].items()}
    # 0-1 launch (the early one); 40-42 topk, 42-50 gather, 50-55 topk,
    # 55-65 fetch, 65-70 topk; 85-95 topk, 95-98 none, 98-100 launch
    assert ms == {"repro.serving.launch": pytest.approx(3),
                  "repro.serving.topk": pytest.approx(22),
                  "repro.serving.gather": pytest.approx(8),
                  "repro.serving.fetch": pytest.approx(10)}
    assert r["rest_s"] == pytest.approx(0.003)


def test_attributed_and_rest_sum_to_the_reducers_idle():
    planes = _serving_planes()
    r = program_spans.attribute(planes)
    reduced = trace.reduce(planes)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert r["idle_total_s"] == pytest.approx(idle, rel=1e-12)
    assert sum(r["idle_s"].values()) + r["rest_s"] == pytest.approx(idle, rel=1e-12)


def test_only_spans_starting_in_the_window_are_counted():
    counts = program_spans.attribute(_serving_planes())["counts"]
    assert counts == {"repro.serving.launch": 2, "repro.serving.topk": 1,
                      "repro.serving.gather": 1, "repro.serving.fetch": 1}


def _run(trace_dir=None, planes=None):
    run = types.SimpleNamespace(trace_dir=trace_dir, ctx={})
    if planes is not None:
        run.trace_dir = "hand-made"
        run.ctx["program_spans"] = program_spans.attribute(planes)
    return run


def test_serving_readers_per_launch():
    run = _run(planes=_serving_planes())
    assert program_spans.fetch_idle_ms(run) == pytest.approx(10 / 2)
    assert program_spans.dispatch_idle_ms(run) == pytest.approx((8 + 3 + 22) / 2)


def test_training_readers_leave_calibrate_to_the_job():
    host = _plane("/host:CPU", {"main": [
        _ev("bench.window", 0, 100),
        _ev("repro.trainer.init", 0, 20),
        _ev("repro.trainer.epoch", 20, 40),
        _ev("repro.trainer.sync", 30, 10),
        _ev("repro.trainer.calibrate", 50, 10),     # inside the first epoch
        _ev("repro.trainer.epoch", 60, 40),
    ]})
    # idle 10-25 (init 10, epoch 5), 35-55 (sync 5, epoch 10, calibrate 5),
    # 90-100 (epoch 10)
    tpu = _plane("/device:TPU:0", {"XLA Ops": [
        _ev("a", 0, 10), _ev("b", 25, 10), _ev("c", 55, 35)]})
    run = _run(planes=[host, tpu])
    assert program_spans.epoch_idle_ms(run) == pytest.approx((5 + 5 + 10 + 10) / 2)
    assert program_spans.job_idle_ms(run) == pytest.approx(10 + 5)


READERS = [program_spans.fetch_idle_ms, program_spans.dispatch_idle_ms,
           program_spans.epoch_idle_ms, program_spans.job_idle_ms]


@pytest.mark.parametrize("reader", READERS)
def test_readers_return_none_without_a_trace(reader):
    assert reader(_run()) is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
@pytest.mark.parametrize("reader", READERS)
def test_readers_return_none_without_program_spans(reader, tmp_path):
    # the recorded trace has the benchmark's spans only, as a program
    # without spans gives
    shutil.copy(DATA, tmp_path / "small.xplane.pb")
    assert reader(_run(trace_dir=str(tmp_path))) is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_v5e_trace_all_rest():
    planes = list(trace.load(DATA))
    r = program_spans.attribute(planes)
    reduced = trace.reduce(planes)
    assert r["idle_s"] == {} and r["counts"] == {}
    assert r["rest_s"] == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-9)
