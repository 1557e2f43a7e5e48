"""Pruned implicit-ALS epochs of one trainer through
``DPMFTrainer.run_epoch()``: the set-up's trainer, past its dense epoch, the
calibration and ``compare_epochs`` pruned epochs, runs whole epochs until
``seconds`` pass.  A window's epoch counts the training log's ratings, as
the funk cell on the same log does; it solves over the log's merged pairs.

The comparison is against the plain iALS reference
(``bench/reference_ials.py``), on what the timed trainer produced:

* ``loss_gap``: the loss over every user x item pair after each epoch of
  the set-up, relative, the worst epoch;
* ``change_gap``: each table's change over the set-up's epochs, as
  ``bench/training.py`` reads it;
* ``solve_gap``: P after the first user half-step, the worst row's
  relative error against the reference's solve.

Every ``TrainConfig`` field that the configuration sets is passed to the
trainer, with the run's seed.  A configuration that sets a field the
reference implements at one value only to another value is refused, and so
is a program whose trainer does not take ``objective="ials"``.
"""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from bench import checks, data, reference, reference_ials, training, work_ials
from bench.harness import Outcome, Refused, free, span

IMPLEMENTED = dict(training.IMPLEMENTED, objective="ials")
FOLLOWED = ("k", "epochs", "lam", "pruning_rate", "implicit_alpha", "init_scale")


def train_config(cfg: dict, seed: int):
    """The ``TrainConfig`` of the configuration, with the run's seed."""
    from repro.core import TrainConfig

    fields = {f.name for f in dataclasses.fields(TrainConfig)} - {"seed"}
    kwargs = {key: cfg[key] for key in fields if key in cfg}
    missing = [key for key in FOLLOWED if key not in cfg]
    if missing:
        raise Refused(f"the configuration does not state {missing}")
    if cfg["init_scale"] != 0.1:
        raise Refused("the reference draws N(0, 0.1^2) factors only")
    for key, value in IMPLEMENTED.items():
        if kwargs.get(key, value) != value:
            raise Refused(f"the reference implements only {key}={value!r}, "
                          f"not {kwargs.get(key)!r}")
    return TrainConfig(**kwargs, seed=seed)


def check(cfg: dict, traffic: dict) -> None:
    train_config(cfg, 0)
    from repro.core import DPMFTrainer, TrainConfig
    from repro.data.ratings import RatingsDataset

    ds = RatingsDataset(np.array([0, 1, 1], np.int32), np.array([1, 0, 0], np.int32),
                        np.ones(3, np.float32), num_users=2, num_items=2)
    try:
        DPMFTrainer(TrainConfig(k=2, epochs=1, objective="ials"), ds)
    except ValueError as exc:
        raise Refused(f"the program does not train objective='ials': {exc}")


def setup(cfg: dict, traffic: dict, seed: int):
    """The trainer the window drives, through its dense epoch and
    ``compare_epochs`` pruned ones, and the program's readings of them."""
    from repro.core import DPMFTrainer
    from repro.data.ratings import RatingsDataset

    s_data, s_train = data.sub_seeds(seed, 2)
    train, test = data.ratings(cfg, s_data)
    train_ds, test_ds = (
        RatingsDataset(*arrays, num_users=cfg["num_users"], num_items=cfg["num_items"],
                       rating_min=cfg["rating_min"], rating_max=cfg["rating_max"])
        for arrays in (train, test))
    trainer = DPMFTrainer(train_config(cfg, s_train), train_ds, test_ds)
    p0, q0 = jnp.copy(trainer.params.p), jnp.copy(trainer.params.q)
    prog = {"loss": []}
    for epoch in range(1 + traffic["compare_epochs"]):
        with span("bench.epoch"):
            record = trainer.run_epoch()
        prog["loss"].append(record.train_abs_err)
        if epoch == 0:
            # P after the first user half-step, its latent axis permuted
            prog["p_half"] = np.asarray(trainer.params.p)
    perm = trainer.perm
    prog["change"] = {"p": float(jnp.linalg.norm(trainer.params.p - p0[:, perm])),
                      "q": float(jnp.linalg.norm(trainer.params.q - q0[:, perm]))}
    prog["t_p"], prog["t_q"] = float(trainer.t_p), float(trainer.t_q)
    prog["perm"] = np.asarray(perm)
    prog["pad_share"], prog["split_rows"] = record.als_pad_share, record.als_split_rows
    del p0, q0
    return trainer, prog, train, s_train


def gaps(prog: dict, ref: dict) -> dict:
    return {
        "loss_gap": reference_ials.loss_gap(prog["loss"], ref["loss"]),
        "change_gap": checks._leaf_gap(prog["change"], ref["change"]),
        "solve_gap": reference_ials.row_gap(prog["p_half"], ref["p_half"][:, prog["perm"]]),
    }


def _window_work(cfg, trainer, train, epochs):
    """The window's required work (``bench/work_ials.py``) at the ranks of
    the state it ended with, over the benchmark's own merged pairs."""
    user, item, _ = reference_ials.merge(np.asarray(train[0]), np.asarray(train[1]),
                                         np.asarray(train[2]), cfg["num_items"])
    r_user = np.asarray(reference.ranks(trainer.params.p, trainer.t_p))
    r_item = np.asarray(reference.ranks(trainer.params.q, trainer.t_q))
    counts = work_ials.epoch_counts(user, item, r_user, r_item)
    return dict(counts, epochs=epochs)


def drive(run, cfg: dict, traffic: dict, seed: int, seconds: float):
    trainer, prog, train, s_train = setup(cfg, traffic, seed)
    per_epoch = int(train[0].shape[0])
    ratings = epochs = 0
    with run.window():
        t0 = time.perf_counter()
        while epochs == 0 or time.perf_counter() - t0 < seconds:
            with span("bench.epoch"):
                trainer.run_epoch()
            ratings += per_epoch
            epochs += 1
    run.reduce_trace()
    last = trainer.history[-1]
    run.log(f"trained {ratings} ratings in {epochs} epochs, {run.window_s:.3f} s; "
            f"last loss a pair {last.train_abs_err:.6g}, test MAE {last.test_mae:.4f}, "
            f"work fraction {last.work_fraction:.4f}, pad share {last.als_pad_share:.4f}, "
            f"split rows {last.als_split_rows:.0f}, epoch wall times "
            f"{[round(r.wall_time_s, 4) for r in trainer.history[-4:]]}")
    if run.trace_dir:
        run.ctx["train"] = {"ratings": ratings, "epochs": epochs}
        run.ctx["ials"] = _window_work(cfg, trainer, train, epochs)
    del trainer
    free()
    ref = reference_ials.train_readings(cfg, train, s_train,
                                        epochs=1 + traffic["compare_epochs"])
    moved = int(np.sum(prog["perm"] != ref["perm"]))
    run.log(f"program loss {prog['loss']}, change {prog['change']}, t_p {prog['t_p']}, "
            f"t_q {prog['t_q']}\nreference loss {ref['loss']}, change {ref['change']}, "
            f"t_p {ref['t_p']}, t_q {ref['t_q']}\nlatent positions whose Algorithm 1 "
            f"permutation differs: {moved}")
    return Outcome(attempted=epochs, failed=0,
                   end_to_end={traffic["metric"]: ratings / run.window_s},
                   readings=gaps(prog, ref))


def controls(cfg: dict, traffic: dict, seed: int):
    """At the cell's own size, each against the float32 reference: the
    reference with its Gramians in one bfloat16 pass in the program's place
    (the lower-precision control), and the reference with half of each
    row's pairs left out and with each row's pairs capped at
    ``reference_ials.CAP`` (planted faults; the cap hits only the hot item
    rows that span many chunks)."""
    s_data, s_train = data.sub_seeds(seed, 2)
    train, _ = data.ratings(cfg, s_data)
    epochs = 1 + traffic["compare_epochs"]
    ref = reference_ials.train_readings(cfg, train, s_train, epochs=epochs)
    out = {}
    for name, kw in (("control_bf16", {"gram": "bf16"}), ("fault_half", {"fault": "half"}),
                     ("fault_cap", {"fault": "cap"})):
        got = reference_ials.train_readings(cfg, train, s_train, epochs=epochs, **kw)
        # both first half-steps' P are unpermuted
        out[name] = gaps(dict(got, perm=np.arange(cfg["k"])), ref)
    return out
