"""Pruned SVD++ epochs of one trainer through ``DPMFTrainer.run_epoch()``:
the set-up's trainer, past its dense epoch, the calibration and its first
``compare_epochs`` epochs, runs whole epochs until ``seconds`` pass.

The comparison follows ``bench/training.py``'s, against the plain SVD++
reference (``bench/reference_svdpp.py``) over the leaves P, Q, Y, b_u and
b_i.  Every ``TrainConfig`` field that the configuration sets is passed to
the trainer; the fields the reference implements at one value only are
``training.IMPLEMENTED``'s, with ``variant="svdpp"``, and a configuration
that sets one of them to another value is refused.  So is a program whose
histories are not the sets N(u) that the reference takes.
"""
import dataclasses
import math
import time

import jax.numpy as jnp
import numpy as np

from bench import checks, data, reference, reference_svdpp, training, work_svdpp
from bench.harness import Outcome, Refused, free, span

IMPLEMENTED = dict(training.IMPLEMENTED, variant="svdpp")
FOLLOWED = training.FOLLOWED + ("max_hist",)
# the reference's leaf: the program's parameter of MFParams / MFOptState
PROGRAM_LEAF = {"p": "p", "q": "q", "y": "implicit", "b_u": "user_bias",
                "b_i": "item_bias"}


def train_config(cfg: dict, seed: int):
    """The ``TrainConfig`` of the configuration, with the run's seed."""
    from repro.core import TrainConfig

    fields = {f.name for f in dataclasses.fields(TrainConfig)} - {"seed"}
    kwargs = {key: cfg[key] for key in fields if key in cfg}
    missing = [key for key in FOLLOWED if key not in kwargs]
    if missing:
        raise Refused(f"the configuration does not state {missing}")
    for key, value in IMPLEMENTED.items():
        if kwargs.get(key, value) != value:
            raise Refused(f"the reference implements only {key}={value!r}, "
                          f"not {kwargs.get(key)!r}")
    return TrainConfig(**kwargs, seed=seed)


def check(cfg: dict, traffic: dict) -> None:
    train_config(cfg, 0)
    from repro.data.ratings import RatingsDataset, build_user_history

    # user 0 rates item 2 twice: N(0) = {2}
    ds = RatingsDataset(np.array([0, 0, 1], np.int32), np.array([2, 2, 0], np.int32),
                        np.ones(3, np.float32), num_users=2, num_items=3)
    out = build_user_history(ds, 2)
    if not (isinstance(out, tuple)
            and np.array_equal(np.asarray(out[0]), [[2, 3], [0, 3]])):
        raise Refused("the program's SVD++ histories are not the sets N(u) "
                      "that the reference implements")


def setup(cfg: dict, traffic: dict, seed: int):
    """The trainer the window drives, through its first ``compare_epochs``
    epochs, and the program's readings of them."""
    from repro.core import DPMFTrainer
    from repro.data.ratings import RatingsDataset

    s_data, s_train = data.sub_seeds(seed, 2)
    train, test = data.ratings(cfg, s_data)
    train_ds, test_ds = (
        RatingsDataset(*arrays, num_users=cfg["num_users"], num_items=cfg["num_items"],
                       rating_min=cfg["rating_min"], rating_max=cfg["rating_max"])
        for arrays in (train, test))
    trainer = DPMFTrainer(train_config(cfg, s_train), train_ds, test_ds)

    def leaves():
        return {leaf: getattr(trainer.params, name) for leaf, name in PROGRAM_LEAF.items()}

    start = {leaf: jnp.copy(x) for leaf, x in leaves().items()}
    prog = {"loss": []}
    for epoch in range(traffic["compare_epochs"]):
        with span("bench.epoch"):
            record = trainer.run_epoch()
        prog["loss"].append(record.train_abs_err)
        if epoch == 0:
            prog["grad"] = {
                leaf: math.sqrt(float(jnp.sum(getattr(trainer.opt_state, name)["acc"])))
                for leaf, name in PROGRAM_LEAF.items()}
    perm = trainer.perm
    for leaf in ("p", "q", "y"):
        start[leaf] = start[leaf][:, perm]
    prog["change"] = {leaf: float(jnp.linalg.norm(x - start[leaf]))
                      for leaf, x in leaves().items()}
    prog["t_p"], prog["t_q"] = float(trainer.t_p), float(trainer.t_q)
    prog["perm"] = np.asarray(perm)
    prog["implicit_rows_share"] = record.implicit_rows_share
    del start
    return trainer, prog, train, s_train


def _window_work(cfg, trainer, train, s_train, first_epoch, epochs):
    """FLOPs and bytes the window's steps require (``bench/work_svdpp.py``)
    at the pair ranks of the state the window ended with, from the
    benchmark's own batches and sets N(u)."""
    user, item, _ = train
    n, k, batch = cfg["num_items"], cfg["k"], cfg["batch_size"]
    hist = jnp.asarray(reference_svdpp.histories(
        np.asarray(user), np.asarray(item), cfg["num_users"], n))
    params = trainer.params
    z = jnp.concatenate([
        reference_svdpp.user_vectors(params.p[lo:lo + batch], params.implicit,
                                     hist[lo:lo + batch])[0]
        for lo in range(0, cfg["num_users"], batch)])
    r_user = reference.ranks(z, trainer.t_p)
    r_item = reference.ranks(params.q, trainer.t_q)
    flops = nbytes = 0
    steps = user.shape[0] // batch
    for epoch in range(first_epoch, first_epoch + epochs):
        order = reference.epoch_order(s_train, epoch, user.shape[0], steps, batch)
        f, b = work_svdpp.step_counts(user[order], item[order], hist, r_user, r_item, k=k)
        flops += int(np.sum(np.asarray(f, np.int64)))
        nbytes += int(np.sum(np.asarray(b, np.int64)))
    return flops, nbytes


def drive(run, cfg: dict, traffic: dict, seed: int, seconds: float):
    trainer, prog, train, s_train = setup(cfg, traffic, seed)
    per_epoch = (train[0].shape[0] // cfg["batch_size"]) * cfg["batch_size"]
    first = trainer.epoch
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
            f"last test MAE {last.test_mae:.4f}, implicit rows share "
            f"{last.implicit_rows_share:.4f}, epoch wall times "
            f"{[round(r.wall_time_s, 4) for r in trainer.history[-4:]]}")
    if run.trace_dir:
        flops, nbytes = _window_work(cfg, trainer, train, s_train, first, epochs)
        run.ctx["train"] = {"ratings": ratings, "steps": ratings // cfg["batch_size"],
                            "batch": cfg["batch_size"]}
        run.ctx["svdpp"] = {"flops": flops, "bytes": nbytes,
                            "implicit_table": f"f32[{cfg['num_items'] + 1},{cfg['k']}]",
                            "batch": cfg["batch_size"], "max_hist": cfg["max_hist"]}
    del trainer
    free()
    ref = reference_svdpp.train_readings(cfg, train, s_train, steps=traffic["compare_epochs"])
    moved = int(np.sum(prog.pop("perm") != ref.pop("perm")))
    run.log(f"program {prog}\nreference {ref}\nlatent positions whose "
            f"Algorithm 1 permutation differs: {moved}")
    return Outcome(attempted=epochs, failed=0,
                   end_to_end={traffic["metric"]: ratings / run.window_s},
                   readings=checks.train_gaps(prog, ref))


def controls(cfg: dict, traffic: dict, seed: int):
    """At the cell's own size: the reference in bfloat16 in the program's
    place (the lower-precision control) and the reference with half of each
    minibatch left out (a planted fault), each against the float32
    reference."""
    s_data, s_train = data.sub_seeds(seed, 2)
    train, _ = data.ratings(cfg, s_data)
    steps = traffic["compare_epochs"]
    ref = reference_svdpp.train_readings(cfg, train, s_train, steps=steps)
    control = reference_svdpp.train_readings(cfg, train, s_train, steps=steps,
                                             dtype=jnp.bfloat16)
    half = reference_svdpp.train_readings(cfg, train, s_train, steps=steps,
                                          fault="half_batch")
    return {"control_bf16": checks.train_gaps(control, ref),
            "fault_half_batch": checks.train_gaps(half, ref)}
