"""The program's host spans (``repro.tracing``): a CPU profiler session
around a serving call and a short training job records each ``repro.*``
span once per layer boundary, nested as the code nests them, with their
counts as stats; and the answers and trained tables are the same bits with
the session on and off."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import mf
from repro.core.trainer import DPMFTrainer, TrainConfig
from repro.data import synthetic_ratings, train_test_split
from repro.serving import ServingEngine


def _spans(trace_dir):
    """(start, end, name, stats) of every ``repro.*`` host event, by start."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats)))
    return sorted(out, key=lambda sp: (sp[0], -sp[1]))


def _parent(spans, child):
    """Name of the shortest other span that covers ``child``, or None."""
    s, e = child[0], child[1]
    covers = [sp for sp in spans if sp is not child and sp[0] <= s and sp[1] >= e]
    return min(covers, key=lambda sp: sp[1] - sp[0])[2] if covers else None


def _traced(tmp_path, fn):
    """``fn()`` inside a profiler session; its result and its spans."""
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    return result, _spans(trace_dir)


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(0)
    params = mf.MFParams(
        jnp.asarray(rng.normal(0, 0.1, (12, 16)).astype(np.float32)),
        jnp.asarray(rng.normal(0, 0.1, (300, 16)).astype(np.float32)),
        None, None, None, None,
    )
    return ServingEngine(params, 0.05, 0.05, max_batch=4, use_kernel=True,
                         interpret=True, block_n=128)


USERS = np.array([3, 0, 11, 7, 5, 2, 9], np.int32)


def test_serving_spans_nest_and_count(engine, tmp_path):
    engine.topk(USERS, 5)   # compile outside the session
    _, spans = _traced(tmp_path, lambda: engine.topk(USERS, 5))
    names = [sp[2] for sp in spans]
    assert names.count("repro.serving.topk") == 1
    assert [sp[3] for sp in spans if sp[2] == "repro.serving.topk"] == [{"users": 7}]
    for name in ("repro.serving.gather", "repro.serving.launch", "repro.serving.fetch"):
        assert names.count(name) == 2
    # per launch, in order: gather, launch, fetch -- each under topk
    per_chunk = [sp[2] for sp in spans if sp[2] != "repro.serving.topk"]
    assert per_chunk == ["repro.serving.gather", "repro.serving.launch",
                         "repro.serving.fetch"] * 2
    assert {_parent(spans, sp) for sp in spans if sp[2] != "repro.serving.topk"} == {
        "repro.serving.topk"}
    launches = [sp[3] for sp in spans if sp[2] == "repro.serving.launch"]
    assert launches == [{"users": 4, "bucket": 4}, {"users": 3, "bucket": 4}]


def test_serving_answers_unchanged_by_the_session(engine, tmp_path):
    off_s, off_i = engine.topk(USERS, 5)
    (on_s, on_i), _ = _traced(tmp_path, lambda: engine.topk(USERS, 5))
    assert off_s.tobytes() == on_s.tobytes()
    assert off_i.tobytes() == on_i.tobytes()


def _trainer():
    ds = synthetic_ratings(30, 40, 600, seed=2)
    train, test = train_test_split(ds, 0.2, seed=2)
    return DPMFTrainer(
        TrainConfig(k=8, epochs=2, batch_size=64, pruning_rate=0.3, seed=5),
        train, test)


def _trained():
    trainer = _trainer()
    trainer.run()
    return trainer


def test_trainer_spans_nest_and_count(tmp_path):
    _trainer().run()   # compile outside the session
    _, spans = _traced(tmp_path, lambda: _trainer().run())
    names = [sp[2] for sp in spans]
    assert {n: names.count(n) for n in set(names)} == {
        "repro.trainer.init": 1, "repro.trainer.epoch": 2,
        "repro.trainer.shuffle": 2, "repro.trainer.step": 2,
        "repro.trainer.sync": 2, "repro.trainer.evaluate": 2,
        "repro.trainer.calibrate": 1,
    }
    parents = {sp[2]: _parent(spans, sp) for sp in spans}
    assert parents == {
        "repro.trainer.init": None, "repro.trainer.epoch": None,
        "repro.trainer.shuffle": "repro.trainer.epoch",
        "repro.trainer.step": "repro.trainer.epoch",
        "repro.trainer.sync": "repro.trainer.epoch",
        "repro.trainer.evaluate": "repro.trainer.epoch",
        "repro.trainer.calibrate": "repro.trainer.epoch",
    }
    epochs = [sp[3] for sp in spans if sp[2] == "repro.trainer.epoch"]
    assert epochs == [{"epoch": 0, "pruned": 0}, {"epoch": 1, "pruned": 1}]
    # calibration follows the first epoch's evaluation, inside that epoch
    (cal,) = [sp for sp in spans if sp[2] == "repro.trainer.calibrate"]
    first = [sp for sp in spans if sp[2] == "repro.trainer.epoch"][0]
    assert first[0] <= cal[0] and cal[1] <= first[1]


def test_trained_tables_unchanged_by_the_session(tmp_path):
    off = _trained()
    on, _ = _traced(tmp_path, _trained)
    for leaf in ("p", "q"):
        assert np.asarray(getattr(off.params, leaf)).tobytes() == \
            np.asarray(getattr(on.params, leaf)).tobytes()
    assert [r.train_abs_err for r in off.history] == [r.train_abs_err for r in on.history]
