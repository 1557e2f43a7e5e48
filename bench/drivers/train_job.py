"""Whole training jobs through ``DPMFTrainer.run()``: the set-up's trainer
runs on to the end of its job, then fresh trainers run whole jobs until
``seconds`` pass."""
import time

from bench import training
from bench.harness import span

check = training.check
controls = training.controls


def drive(run, cfg: dict, traffic: dict, seed: int, seconds: float):
    trainer, new_trainer, prog, train, s_train, per_epoch = training.setup(
        run, cfg, traffic, seed)
    ratings = epochs = dense = jobs = 0
    with run.window():
        t0 = time.perf_counter()
        while jobs == 0 or time.perf_counter() - t0 < seconds:
            with span("bench.job"):
                if jobs:
                    trainer = new_trainer()
                first = trainer.epoch
                trainer.run()
            done = cfg["epochs"] - first
            epochs += done
            dense += first == 0
            ratings += per_epoch * done
            jobs += 1
    run.reduce_trace()
    run.log(f"{jobs} jobs in the window")
    return training.finish(
        run, cfg, traffic, trainer, prog, train, s_train, ratings, epochs,
        [(per_epoch * dense, True), (per_epoch * (epochs - dense), False)],
    )
