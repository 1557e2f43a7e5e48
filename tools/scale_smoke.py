"""CI smoke for the out-of-core data path (the `scale-smoke` job).

Three gates, each fatal on failure:

1. **store build + streamed training** — converts a synthetic ratings
   dataset into an on-disk columnar store and trains two epochs from it
   through the bounded-prefetch slab loader;
2. **mid-epoch kill + resume, bitwise** — repeats the run but kills the
   process-equivalent (a ``KeyboardInterrupt`` injected into the slab scan)
   partway through epoch 1, restores from the mid-epoch checkpoint, and
   asserts every parameter/optimizer array AND the logged epoch metrics are
   bitwise identical to the uninterrupted run;
3. **eviction-armed online launcher** — runs ``repro.launch.online``'s
   entry point in this process (a child would need the accelerator this
   process already holds) with ``--evict-max-users`` small enough that the
   poisson new-user stream forces live eviction/compaction rounds, and
   checks the report says so.

Usage:  PYTHONPATH=src python tools/scale_smoke.py
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import repro.core.trainer as trainer_lib
from repro.core.trainer import DPMFTrainer, TrainConfig
from repro.data import synthetic_ratings
from repro.launch import online as online_launch
from repro.store import RatingsStore, build_store


def _cfg(store_dir: str, ckpt_dir: str | None) -> TrainConfig:
    return TrainConfig(
        k=8, epochs=2, batch_size=64, lr=0.05, lam=0.02, pruning_rate=0.5,
        seed=0, store_dir=store_dir, slab_steps=4, prefetch_slabs=2,
        checkpoint_dir=ckpt_dir, checkpoint_every_epochs=1,
        checkpoint_every_slabs=2,
    )


def _train(store_dir: str, ckpt_dir: str | None, *, kill_after: int = 0):
    """Train 2 epochs; if kill_after > 0, raise after that many slab scans."""
    trainer = DPMFTrainer(_cfg(store_dir, ckpt_dir))
    resumed = trainer.maybe_restore()
    if resumed:
        print(f"  resumed at epoch {trainer.epoch} "
              f"slab {trainer._resume_slab}")
    calls = {"n": 0}
    original = trainer_lib.mf.train_epoch_scan

    def counting(*args, **kwargs):
        calls["n"] += 1
        if kill_after and calls["n"] > kill_after:
            raise KeyboardInterrupt("injected mid-epoch kill")
        return original(*args, **kwargs)

    trainer_lib.mf.train_epoch_scan = counting
    try:
        while trainer.epoch < trainer.config.epochs:
            trainer.run_epoch()
    except KeyboardInterrupt:
        print(f"  killed after {kill_after} slab scans")
    finally:
        trainer_lib.mf.train_epoch_scan = original
        if trainer._ckpt is not None:
            trainer._ckpt.wait()
    return trainer


def _assert_bitwise(a: DPMFTrainer, b: DPMFTrainer) -> None:
    pairs = [("params.p", a.params.p, b.params.p),
             ("params.q", a.params.q, b.params.q)]
    for name, x, y in pairs:
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"{name} diverged after resume")
    for group in a.opt_state._fields:
        ga, gb = getattr(a.opt_state, group), getattr(b.opt_state, group)
        if isinstance(ga, dict):
            for key in ga:
                assert np.array_equal(np.asarray(ga[key]),
                                      np.asarray(gb[key])), (
                    f"opt_state.{group}[{key}] diverged after resume")
    ra, rb = a.history[-1], b.history[-1]
    assert ra.train_abs_err == rb.train_abs_err, (
        f"epoch metric diverged: {ra.train_abs_err!r} vs "
        f"{rb.train_abs_err!r}")
    print("  bitwise parity: params, opt_state, epoch metrics all equal")


def main() -> int:
    workdir = tempfile.mkdtemp(prefix="scale_smoke_")
    try:
        # ---- gate 1: build a store and stream-train from it --------------
        print("[1/3] build store + streamed 2-epoch training")
        ds = synthetic_ratings(400, 120, 4096, seed=0)
        store_dir = os.path.join(workdir, "store")
        build_store(ds, store_dir)
        store = RatingsStore(store_dir)
        assert len(store) == len(ds), "store lost ratings"
        baseline = _train(store_dir, None)
        assert len(baseline.history) == 2
        print(f"  mae trajectory: "
              f"{[round(r.test_mae, 4) for r in baseline.history]}")

        # ---- gate 2: kill mid-epoch-1, resume, demand bitwise parity -----
        print("[2/3] mid-epoch kill + resume (bitwise)")
        ckpt_dir = os.path.join(workdir, "ckpt")
        # epoch 0 has num_slabs scans; kill 3 scans into epoch 1, after the
        # slab-2 mid-epoch checkpoint has been written
        num_slabs = baseline._loader.num_slabs
        assert num_slabs >= 4, f"need >=4 slabs for a mid-epoch kill"
        _train(store_dir, ckpt_dir, kill_after=num_slabs + 3)
        resumed = _train(store_dir, ckpt_dir)
        _assert_bitwise(baseline, resumed)

        # ---- gate 3: online launcher with eviction armed -----------------
        print("[3/3] launch.online with cold-row eviction armed")
        report_path = os.path.join(workdir, "online_report.json")
        # exits through SystemExit (non-zero) on any failed request
        online_launch.main(
            ["--train-epochs", "2", "--events", "640", "--batch-events", "16",
             "--swap-every", "4", "--source", "poisson",
             "--new-id-prob", "0.5", "--evict-max-users", "60",
             "--json", report_path]
        )
        with open(report_path) as f:
            report = json.load(f)
        ev = report.get("eviction")
        assert ev is not None, "report missing eviction section"
        assert ev["rounds"] >= 1, "eviction never triggered — smoke too small"
        assert ev["physical_users"] <= 60, "eviction failed to bound residency"
        print(f"  eviction rounds={ev['rounds']} evicted={ev['evicted_total']}"
              f" live={ev['physical_users']} remap_epoch={ev['remap_epoch']}")
        print("scale-smoke: all gates passed")
        return 0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
