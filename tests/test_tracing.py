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


def _svdpp_trainer():
    ds = synthetic_ratings(30, 40, 600, seed=2)
    train, test = train_test_split(ds, 0.2, seed=2)
    return DPMFTrainer(
        TrainConfig(k=8, epochs=2, batch_size=64, pruning_rate=0.3, seed=5,
                    variant="svdpp", max_hist=8),
        train, test)


def test_history_span_counts_users_slots_and_cut(tmp_path):
    """``repro.trainer.history`` wraps the SVD++ history build inside
    ``repro.trainer.init``, with the users, the live slots (distinct
    training pairs kept), the users cut at ``max_hist`` and the capacity
    of history slots a step runs on (all 64 x 8: the histories are full)."""
    trainer, spans = _traced(tmp_path, _svdpp_trainer)
    (hist,) = [sp for sp in spans if sp[2] == "repro.trainer.history"]
    assert _parent(spans, hist) == "repro.trainer.init"
    ds = trainer.train_ds
    sets = [set(ds.item[ds.user == u].tolist()) for u in range(ds.num_users)]
    assert hist[3] == {
        "users": 30,
        "slots": sum(min(len(s), 8) for s in sets),
        "truncated": sum(len(s) > 8 for s in sets),
        "capacity": 64 * 8,
    }
    assert hist[3]["truncated"] > 0   # 16 training ratings a user: some cut


def test_svdpp_epochs_record_packed_steps(tmp_path):
    """With sparse histories (about 5 of 32 slots live) the trainer's steps
    run Y's side on the live slots packed to its capacity: the history span
    carries the capacity, 2,048 of a step's 256 x 32 slots, and every
    epoch records ``implicit_compact_share`` 1; the funk trainer has none."""
    ds = synthetic_ratings(400, 600, 2400, seed=4)
    train, test = train_test_split(ds, 0.2, seed=4)
    config = TrainConfig(k=8, epochs=2, batch_size=256, pruning_rate=0.3,
                         seed=5, variant="svdpp", max_hist=32)
    trainer, spans = _traced(tmp_path, lambda: DPMFTrainer(config, train, test))
    (hist,) = [sp for sp in spans if sp[2] == "repro.trainer.history"]
    sizes = [len(set(train.item[train.user == u].tolist())) for u in train.user]
    need = 256 * np.mean(sizes) + 6 * 16 * np.std(sizes)
    assert 1024 < need <= 2048
    assert hist[3]["capacity"] == 2048
    records = trainer.run()
    assert [r.implicit_compact_share for r in records] == [1.0, 1.0]
    assert np.isnan(_trainer().run_epoch().implicit_compact_share)


def test_implicit_rows_share_counts_distinct_rows_of_y():
    """The epoch record's ``implicit_rows_share`` is the mean over steps of
    the distinct rows of Y written over the live history occurrences; the
    funk trainer has none."""
    trainer = _svdpp_trainer()
    batches = trainer._packed_train.epoch_batches(trainer.config.seed, 0)
    record = trainer.run_epoch()   # dense: every occurrence of a set is live
    hist = trainer.hist
    shares = []
    for users in np.asarray(batches["user"]):
        slots = hist[users].reshape(-1)
        live = slots[slots < trainer.train_ds.num_items]
        shares.append(np.unique(live).size / live.size)
    assert record.implicit_rows_share == pytest.approx(np.mean(shares), rel=1e-6)
    assert np.isnan(_trainer().run_epoch().implicit_rows_share)


def _ials_trainer():
    ds = synthetic_ratings(40, 30, 900, seed=3)
    train, test = train_test_split(ds, 0.2, seed=3)
    return DPMFTrainer(
        TrainConfig(k=8, epochs=2, pruning_rate=0.3, seed=5, objective="ials", lam=0.1),
        train, test)


def test_ials_spans_and_counters(tmp_path):
    """``repro.trainer.als`` wraps each half-step's dispatch inside
    ``repro.trainer.epoch``, users then items, with the side, its rows that
    have pairs and its chunks; the epoch records carry the layouts' padding
    share and split rows, and the solved ranks' mean as the work fraction;
    the funk trainer has no such counters."""
    def trained():
        trainer = _ials_trainer()
        trainer.run()
        return trainer

    trained()   # compile outside the session
    trainer, spans = _traced(tmp_path, trained)
    als_spans = [sp for sp in spans if sp[2] == "repro.trainer.als"]
    assert [sp[3]["side"] for sp in als_spans] == ["user", "item"] * 2
    assert {_parent(spans, sp) for sp in als_spans} == {"repro.trainer.epoch"}
    user, item = trainer._pairs.user, trainer._pairs.item
    assert als_spans[0][3] == {"side": "user", "rows": user.solved_rows, "chunks": user.chunks}
    assert als_spans[1][3] == {"side": "item", "rows": item.solved_rows, "chunks": item.chunks}
    ds = trainer.train_ds
    pairs = {(u, i) for u, i in zip(ds.user.tolist(), ds.item.tolist())}
    counts_u = np.bincount([u for u, _ in pairs], minlength=ds.num_users)
    counts_i = np.bincount([i for _, i in pairs], minlength=ds.num_items)
    assert user.solved_rows == int(np.sum(counts_u > 0))
    slots = (np.sum(-(-counts_u // user.chunk)) + np.sum(-(-counts_i // item.chunk))) * user.chunk
    split = int(np.sum(counts_u > user.chunk) + np.sum(counts_i > item.chunk))
    for record in trainer.history:
        assert record.als_pad_share == pytest.approx(1 - 2 * len(pairs) / slots)
        assert record.als_split_rows == split
    assert trainer.history[0].work_fraction == 1.0
    assert 0 < trainer.history[1].work_fraction < 1.0
    funk = _trainer().run_epoch()
    assert np.isnan(funk.als_pad_share) and np.isnan(funk.als_split_rows)
