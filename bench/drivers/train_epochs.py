"""Pruned epochs of one trainer through ``DPMFTrainer.run_epoch()``: the
set-up's trainer, past its dense epoch and calibration, runs whole epochs
until ``seconds`` pass."""
import time

from bench import training
from bench.harness import span

check = training.check
controls = training.controls


def drive(run, cfg: dict, traffic: dict, seed: int, seconds: float):
    trainer, _, prog, train, s_train, per_epoch = training.setup(run, cfg, traffic, seed)
    ratings = epochs = 0
    with run.window():
        t0 = time.perf_counter()
        while epochs == 0 or time.perf_counter() - t0 < seconds:
            with span("bench.epoch"):
                trainer.run_epoch()
            ratings += per_epoch
            epochs += 1
    run.reduce_trace()
    return training.finish(run, cfg, traffic, trainer, prog, train, s_train,
                           ratings, epochs, [(per_epoch * epochs, False)])
