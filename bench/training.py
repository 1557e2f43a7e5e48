"""What the training drivers share: the trainer the window drives, built
from the configuration, the comparison of its first epochs with the
reference, and the readings the limits are calibrated from.

Every ``TrainConfig`` field that a configuration sets is passed to the
trainer.  The reference follows the fields in ``FOLLOWED`` and implements
the fields in ``IMPLEMENTED`` at one value only; a configuration that sets
one of those to another value is refused, not run unchecked.  The other
fields (``use_fused_kernel``, ``epoch_mode``, ...) change how the program
computes, not what, and the comparison holds the program to the same
reference whatever they are.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as np

from bench import checks, data, reference
from bench.harness import Outcome, Refused, Run, free, span

FOLLOWED = ("k", "epochs", "batch_size", "lr", "lam", "pruning_rate")
IMPLEMENTED = {
    "optimizer": "adagrad", "variant": "funk", "objective": "explicit",
    "strategy": "standard", "init_method": "normal", "rearrange": True,
    "grad_compression": "none", "store_dir": None, "checkpoint_dir": None,
}


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
                          f"not {kwargs[key]!r}")
    return TrainConfig(**kwargs, seed=seed)


def check(cfg: dict, traffic: dict) -> None:
    train_config(cfg, 0)


def setup(run: Run, cfg: dict, traffic: dict, seed: int):
    """The trainer the window drives, driven through its first
    ``compare_epochs`` epochs, and the program's readings of them."""
    from repro.core import DPMFTrainer
    from repro.data.ratings import RatingsDataset

    s_data, s_train = data.sub_seeds(seed, 2)
    train, test = data.ratings(cfg, s_data)

    def dataset(arrays):
        return RatingsDataset(
            *arrays, num_users=cfg["num_users"], num_items=cfg["num_items"],
            rating_min=cfg["rating_min"], rating_max=cfg["rating_max"],
        )

    tcfg = train_config(cfg, s_train)
    train_ds, test_ds = dataset(train), dataset(test)

    def new_trainer():
        return DPMFTrainer(tcfg, train_ds, test_ds)

    # the object the window drives, through its first epochs
    trainer = new_trainer()
    p0, q0 = jnp.copy(trainer.params.p), jnp.copy(trainer.params.q)
    prog = {"loss": []}
    for epoch in range(traffic["compare_epochs"]):
        with span("bench.epoch"):
            record = trainer.run_epoch()
        prog["loss"].append(record.train_abs_err)
        if epoch == 0:
            prog["grad"] = {
                leaf: math.sqrt(float(jnp.sum(getattr(trainer.opt_state, leaf)["acc"])))
                for leaf in ("p", "q")
            }
    perm = trainer.perm
    prog["t_p"], prog["t_q"] = float(trainer.t_p), float(trainer.t_q)
    prog["perm"] = np.asarray(perm)
    prog["change"] = {
        "p": float(jnp.linalg.norm(trainer.params.p - p0[:, perm])),
        "q": float(jnp.linalg.norm(trainer.params.q - q0[:, perm])),
    }
    del p0, q0
    per_epoch = (train[0].shape[0] // cfg["batch_size"]) * cfg["batch_size"]
    return trainer, new_trainer, prog, train, s_train, per_epoch


def finish(run, cfg, traffic, trainer, prog, train, s_train, ratings,
           epochs, epoch_ranks):
    """The window's end-to-end rate under the traffic's ``metric`` name,
    the per-layer readers' context, and the comparison."""
    elapsed = run.window_s
    run.log(f"trained {ratings} ratings in {epochs} epochs, {elapsed:.3f} s; "
            f"last test MAE {trainer.history[-1].test_mae:.4f}, "
            f"epoch wall times {[round(r.wall_time_s, 4) for r in trainer.history[-4:]]}")
    if run.trace_dir:
        # rank histogram of the state the window ended with, counted by the
        # benchmark's own rank rule
        r_u = reference.ranks(trainer.params.p, trainer.t_p)
        r_i = reference.ranks(trainer.params.q, trainer.t_q)
        pruned_rank = float(jnp.mean(jnp.minimum(r_u[train[0]], r_i[train[1]])))
        run.ctx["train"] = {
            "ratings": ratings,
            "steps": ratings // cfg["batch_size"],
            "batch": cfg["batch_size"],
            # pair rank of each epoch's work: k for dense epochs
            "pair_ranks": [(n, cfg["k"] if dense else pruned_rank)
                           for n, dense in epoch_ranks],
        }
    del trainer
    free()
    ref = reference.train_readings(cfg, train, s_train, steps=traffic["compare_epochs"])
    moved = int(np.sum(prog.pop("perm") != ref.pop("perm")))
    run.log(f"program {prog}\nreference {ref}\nlatent positions whose "
            f"Algorithm 1 permutation differs: {moved}")
    return Outcome(
        attempted=epochs, failed=0,
        end_to_end={traffic["metric"]: ratings / elapsed},
        readings=checks.train_gaps(prog, ref),
    )


def controls(cfg: dict, traffic: dict, seed: int):
    """At the cell's own size: the reference in bfloat16 in the program's
    place (the lower-precision control) and the reference with half of each
    minibatch left out (a planted fault), each against the float32
    reference.  A step that returns its state unchanged reads 1 on
    ``change_gap`` and needs no run."""
    s_data, s_train = data.sub_seeds(seed, 2)
    train, _ = data.ratings(cfg, s_data)
    ref = reference.train_readings(cfg, train, s_train)
    control = reference.train_readings(cfg, train, s_train, dtype=jnp.bfloat16)
    half = reference.train_readings(cfg, train, s_train, fault="half_batch")
    return {"control_bf16": checks.train_gaps(control, ref),
            "fault_half_batch": checks.train_gaps(half, ref)}
