#!/usr/bin/env python3
"""Main-path smoke on a TPU: train -> serve -> refresh, in one process.

    python chip_smoke.py                # one chip: the whole main path
    python chip_smoke.py --four-chips   # four chips: the sharded paths only

One chip.  Everything goes through the objects the launchers build, at the
dpmf config's full width (k=128) on the paper's BookCrossings shape
(105,284 users x 340,554 items x 1,149,779 ratings, generated from
``--seed``):

* **train** -- ``DPMFTrainer`` with Adagrad at pruning rate 0.3 for three
  epochs: a dense epoch, the threshold solve and the latent
  rearrangement, then two pruned epochs through ``train_epoch_scan``;
* **serve** -- checkpoint -> ``ServingEngine.from_checkpoint`` -> top-100
  for 1,024 users through the Pallas ``pruned_topk`` kernel, compared with
  the streaming path, the dense oracle and a float64 host rescoring, at
  the trained thresholds and at rate 0;
* **async** -- a few dozen requests through ``start``/``submit``/``stop``;
* **refresh** -- ``OnlineUpdater`` micro-batches published by
  ``SnapshotPublisher`` while the queue serves; the in-place patched
  layout must answer exactly like a freshly built engine.

Four chips (``--four-chips``).  ``ServingEngine.topk_sharded`` on a (4,)
"model" mesh and a 2x2 ("data", "model") mesh against single-device
``topk``, and the owner-compute ``train_epoch_scan_shard_map`` (fp32 and
``int8_ef``) against ``train_epoch_scan`` on the same batches.

The script refuses any platform but TPU (exit 2, no result line), and
exits 1 when a phase raises or a check fails.  Times it prints are a
smoke's, split into compile and run seconds; they are not metrics.  Its
last line on success is ``{"ok": true, "device": {...}}``.  Checkpoints go
under ``--out`` (default ``.chip_smoke/`` in the checkout, git-ignored).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

TOPK = 100
N_USERS_SERVED = 1024
GOLD_USERS = 128        # users whose exact float64 top-k is computed on host
ASYNC_REQUESTS = 48
EVENT_BATCH = 1024
EVENT_BATCHES = 4
OWNER_STEPS, OWNER_BATCH = 32, 4096   # owner-compute epoch (four chips)
# float32 dot-product forward error: |fl(p.q) - p.q| <= k * u * sum|p_t q_t|
# with unit roundoff u = 2**-24 (Higham, Accuracy and Stability, 3.1)
F32_UNIT_ROUNDOFF = 2.0 ** -24


class Smoke:
    """Phase timer and check ledger.  A failed check does not stop the run
    (later phases still report), but it makes the script exit non-zero."""

    def __init__(self, jax):
        self.jax = jax
        self.failed = []
        self.compile_s = 0.0
        self._lock = threading.Lock()   # compiles are reported from several threads
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += duration

    def check(self, name, ok, detail=""):
        print(f"  [{'pass' if ok else 'FAIL'}] {name}" + (f": {detail}" if detail else ""))
        if not ok:
            self.failed.append(name)

    @contextlib.contextmanager
    def phase(self, name):
        print(f"[{name}]", flush=True)
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0   # summed over threads, so it can exceed wall
        stats = self.jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", 0) / 2**30
        print(f"  ({name}: wall {wall:.1f} s, of which XLA compile {comp:.1f} s, "
              f"run {max(wall - comp, 0.0):.1f} s; smoke timings, not metrics; "
              f"device peak_bytes_in_use {peak:.2f} GiB)", flush=True)


# ---------------------------------------------------------------------------
# float64 host reference
# ---------------------------------------------------------------------------


def exact_pair_scores(p_rows, r_u, q, r_i, items):
    """float64 masked scores of (user row, item) pairs and the float32 error
    bound of each.  ``p_rows`` (B, k), ``items`` (B, K)."""
    k = p_rows.shape[1]
    p64 = p_rows.astype(np.float64)
    q64 = q[items].astype(np.float64)                         # (B, K, k)
    lim = np.minimum(r_u[:, None], r_i[items])                # (B, K)
    mask = np.arange(k)[None, None, :] < lim[..., None]
    prod = p64[:, None, :] * q64 * mask
    return prod.sum(-1), k * F32_UNIT_ROUNDOFF * np.abs(prod).sum(-1)


def precision_probe(smoke, interpret):
    """Which float32 matmul precision each kind of path gets on this chip:
    the error of a (256, 128) x (128, 512) product against float64, as a
    share of the float32 dot bound.  The HIGHEST-pinned forms (what the
    oracle, the streaming path and the kernels use) must be within it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    highest = jax.lax.Precision.HIGHEST
    rng = np.random.default_rng(0)
    a = rng.standard_normal((256, 128)).astype(np.float32)
    b = rng.standard_normal((512, 128)).astype(np.float32)
    prod = a.astype(np.float64)[:, None, :] * b.astype(np.float64)[None]
    exact = prod.sum(-1)
    bound = a.shape[1] * F32_UNIT_ROUNDOFF * np.abs(prod).sum(-1)

    def pallas_dot(precision):
        def kernel(a_ref, b_ref, o_ref):
            o_ref[...] = jax.lax.dot_general(
                a_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(exact.shape, jnp.float32),
            interpret=interpret)

    paths = {
        "XLA default": jax.jit(lambda x, y: jnp.dot(x, y.T)),
        "XLA HIGHEST": jax.jit(lambda x, y: jnp.dot(x, y.T, precision=highest)),
        "Pallas default": pallas_dot(None),
        "Pallas HIGHEST": pallas_dot(highest),
    }
    for name, fn in paths.items():
        ratio = float(np.max(np.abs(np.asarray(fn(a, b)) - exact) / bound))
        detail = f"max error {ratio:.3f} of the float32 dot bound"
        if name.endswith("HIGHEST"):
            smoke.check(f"{name} float32 matmul is float32-accurate", ratio <= 1.0, detail)
        else:
            print(f"  {name} float32 matmul: {detail} -> "
                  f"{'float32-accurate' if ratio <= 1.0 else 'reduced precision'}")


def exact_topk(p_rows, r_u, q, r_i, topk):
    """Brute-force float64 top-k on the host (the gold ranking)."""
    k = p_rows.shape[1]
    pm = p_rows.astype(np.float64) * (np.arange(k)[None, :] < r_u[:, None])
    qm = q.astype(np.float64) * (np.arange(k)[None, :] < r_i[:, None])
    scores = pm @ qm.T
    part = np.argpartition(-scores, topk - 1, axis=1)[:, :topk]
    order = np.argsort(-np.take_along_axis(scores, part, 1), axis=1, kind="stable")
    return np.take_along_axis(part, order, 1)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def run_one_chip(smoke, args):
    import jax.numpy as jnp

    from repro.configs.dpmf import CONFIG as DPMF
    from repro.core.ranks import effective_ranks
    from repro.core.trainer import DPMFTrainer, TrainConfig
    from repro.data.ratings import paper_dataset, train_test_split
    from repro.kernels import ops as kops
    from repro.kernels.pruned_topk import pruned_topk_padded
    from repro.online import (
        OnlineUpdater,
        ReplaySource,
        SnapshotPublisher,
        iter_microbatches,
    )
    from repro.serving import ServingEngine

    rng = np.random.default_rng(args.seed)
    ckpt_dir = os.path.join(args.out, "ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)   # this run's checkpoints only

    with smoke.phase("precision"):
        precision_probe(smoke, kops._default_interpret())

    with smoke.phase("data"):
        ds = paper_dataset("bookcrossings", seed=args.seed)
        train_ds, test_ds = train_test_split(ds, 0.2, seed=args.seed)
        shape = (ds.num_users, ds.num_items, len(ds))
        smoke.check("BookCrossings shape", shape == (105284, 340554, 1149779),
                    f"{shape[0]} users x {shape[1]} items x {shape[2]} ratings")

    config = TrainConfig(
        k=DPMF.k, epochs=3, lr=DPMF.lr, lam=DPMF.lam,
        optimizer=DPMF.optimizer, pruning_rate=DPMF.pruning_rate,
        seed=args.seed, checkpoint_dir=ckpt_dir,
    )
    with smoke.phase("train"):
        trainer = DPMFTrainer(config, train_ds, test_ds)
        for rec in trainer.run():
            print(f"  epoch {rec.epoch}: train |err| {rec.train_abs_err:.4f}  "
                  f"test MAE {rec.test_mae:.4f}  work {rec.work_fraction:.3f}  "
                  f"t_p {rec.t_p:.4f} t_q {rec.t_q:.4f}  wall {rec.wall_time_s:.2f} s")
        hist = trainer.history
        smoke.check("finite epoch metrics", all(
            np.isfinite(r.train_abs_err) and np.isfinite(r.test_mae) for r in hist))
        smoke.check("epoch 0 dense, later epochs pruned",
                    hist[0].work_fraction == 1.0
                    and all(r.t_q > 0 and r.work_fraction < 1.0 for r in hist[1:]),
                    f"work fractions {[round(r.work_fraction, 3) for r in hist]}")
        smoke.check("finite factors", bool(
            jnp.isfinite(trainer.params.p).all() & jnp.isfinite(trainer.params.q).all()))

    users = np.sort(rng.choice(ds.num_users, N_USERS_SERVED, replace=False)).astype(np.int32)
    with smoke.phase("serve"):
        engine = ServingEngine.from_checkpoint(ckpt_dir)
        smoke.check("engine picks the Pallas kernel, compiled",
                    engine.use_kernel and not engine._interpret())
        smoke.check("FunkSVD checkpoint (no bias terms)", engine.params.user_bias is None)
        t0 = time.perf_counter()
        engine.topk(users, TOPK)
        t1 = time.perf_counter()
        k_s, k_i = engine.topk(users, TOPK)
        print(f"  engine.topk({N_USERS_SERVED} users, top-{TOPK}): first call "
              f"{t1 - t0:.2f} s, second {time.perf_counter() - t1:.3f} s")
        snap = engine._snap
        pu = engine.params.p[jnp.asarray(users[:256])]
        pp, rup = kops.pad_users_for_topk_kernel(pu, effective_ranks(pu, snap.t_p))
        qp, rip, biasp = snap.kernel_layout()
        text = pruned_topk_padded.lower(
            pp, qp, rup, rip, biasp, topk=TOPK, n_items=snap.n_items,
            interpret=engine._interpret(),
        ).as_text()
        smoke.check("tpu_custom_call in the scoring program", "tpu_custom_call" in text)

        p_np = np.asarray(engine.params.p)
        q_np = np.asarray(engine.params.q)
        for label, t_p, t_q in (("trained thresholds", snap.t_p, snap.t_q),
                                ("rate 0", 0.0, 0.0)):
            _parity(smoke, label, engine.params, p_np, q_np, users,
                    jnp.float32(t_p), jnp.float32(t_q),
                    kernel=(k_s, k_i) if label != "rate 0" else None)

    with smoke.phase("async"):
        engine.start()
        futs = [(int(u), engine.submit(int(u), TOPK)) for u in users[:ASYNC_REQUESTS]]
        rows = {int(u): i for i, u in enumerate(users)}
        results = [(u, f.result(timeout=600)) for u, f in futs]
        engine.stop()
        same = all(np.array_equal(np.asarray(items), k_i[rows[u]])
                   and np.array_equal(np.asarray(scores), k_s[rows[u]])
                   for u, (scores, items) in results)
        smoke.check("async answers == sync topk rows, byte-identical", same,
                    f"{len(results)} requests")

    with smoke.phase("refresh"):
        updater = OnlineUpdater.from_trainer(trainer, batch_size=EVENT_BATCH)
        publisher = SnapshotPublisher(engine, updater)
        engine.start()
        stop = threading.Event()
        served, errors = [0], []

        def client():
            crng = np.random.default_rng(args.seed + 1)
            while not stop.is_set():
                try:
                    engine.submit(int(crng.choice(users)), TOPK).result(timeout=600)
                    served[0] += 1
                except Exception as exc:  # noqa: BLE001 -- reported as a failed check
                    errors.append(repr(exc))

        thread = threading.Thread(target=client, daemon=True)
        thread.start()
        source = ReplaySource(test_ds, shuffle=True, seed=args.seed)
        reports = []
        for b, batch in enumerate(iter_microbatches(
                source, EVENT_BATCH, max_events=EVENT_BATCH * EVENT_BATCHES)):
            m = updater.apply(batch)
            if b % 2 == 1:
                reports.append(publisher.publish())
                r = reports[-1]
                print(f"  publish v{r.version}: {r.touched_users} users / "
                      f"{r.touched_items} items touched, kind {r.kind}, "
                      f"swap {r.swap_s * 1e3:.1f} ms; batch |err| {m['abs_err']:.4f}")
        stop.set()
        thread.join(timeout=600)
        engine.stop()
        publisher.close()
        smoke.check("requests served during refresh, none failed",
                    served[0] > 0 and not errors and not thread.is_alive(),
                    f"{served[0]} served, {len(errors)} failed {errors[:2]}")
        smoke.check("engine at the published version",
                    engine.version == len(reports) == EVENT_BATCHES // 2,
                    f"v{engine.version}")
        smoke.check("incremental swaps patched the kernel layout in place",
                    all(not r.full_rebuild and r.touched_items > 0 for r in reports))
        fresh = ServingEngine(updater.params, updater.t_p, updater.t_q)
        g_s, g_i = engine.topk(users[:256], TOPK)
        f_s, f_i = fresh.topk(users[:256], TOPK)
        smoke.check("patched engine == freshly built engine, byte-identical",
                    np.array_equal(g_i, f_i) and np.array_equal(g_s, f_s))
        moved = float(np.mean(g_i != k_i[:256]))
        print(f"  top-{TOPK} entries changed by the refresh: {moved:.4f}")


def _parity(smoke, label, params, p_np, q_np, users, t_p, t_q, *, kernel):
    """Kernel vs streaming vs dense oracle vs float64 host scores."""
    import jax.numpy as jnp

    from repro.core.ranks import effective_ranks
    from repro.eval.ranking import dense_topk
    from repro.serving import ServingEngine

    print(f"  -- parity at {label} (t_p {float(t_p):.4f}, t_q {float(t_q):.4f})")
    if kernel is None:
        kernel = ServingEngine(params, t_p, t_q).topk(users, TOPK)
    stream = ServingEngine(params, t_p, t_q, use_kernel=False).topk(users, TOPK)
    chunks = [dense_topk(params, users[i:i + 256], TOPK, t_p=t_p, t_q=t_q)
              for i in range(0, len(users), 256)]
    oracle = tuple(np.concatenate(c) for c in zip(*chunks))
    r_u = np.asarray(effective_ranks(params.p[jnp.asarray(users)], t_p))
    r_i = np.asarray(effective_ranks(params.q, t_q))
    p_rows = p_np[users]

    paths = {"kernel": kernel, "stream": stream, "oracle": oracle}
    exact, bound = {}, {}
    for name, (s, i) in paths.items():
        exact[name], bound[name] = exact_pair_scores(p_rows, r_u, q_np, r_i, i)
        err = np.abs(s - exact[name])
        ratio = float(np.max(err / np.maximum(bound[name], 1e-30)))
        smoke.check(f"{name} scores within the float32 dot bound",
                    ratio <= 1.0,
                    f"max |s - s64| {err.max():.3e}, {ratio:.3f} of the bound")
    tol = 2.0 * max(float(b.max()) for b in bound.values())
    gold_i = exact_topk(p_rows[:GOLD_USERS], r_u[:GOLD_USERS], q_np, r_i, TOPK)
    gold, _ = exact_pair_scores(p_rows[:GOLD_USERS], r_u[:GOLD_USERS], q_np, r_i, gold_i)
    for name in paths:
        # a valid top-k up to near-ties: the sorted exact scores of the
        # returned items equal the gold top-k's within two error bounds
        got = -np.sort(-exact[name][:GOLD_USERS], axis=1)
        dev = float(np.max(np.abs(got - gold)))
        smoke.check(f"{name} top-{TOPK} == float64 gold top-{TOPK} "
                    f"({GOLD_USERS} users, up to ties within {tol:.2e})",
                    dev <= tol, f"max deviation {dev:.3e}")
    for name in ("stream", "oracle"):
        s, i = paths[name]
        agree = float(np.mean(kernel[1] == i))
        same = kernel[1] == i
        ds = float(np.max(np.abs(kernel[0] - s)[same])) if same.any() else 0.0
        sets = -np.sort(-exact["kernel"], 1) - (-np.sort(-exact[name], 1))
        smoke.check(f"kernel vs {name}: same top-{TOPK} up to ties",
                    float(np.max(np.abs(sets))) <= tol,
                    f"index agreement {agree:.6f}, max score diff on agreeing "
                    f"entries {ds:.3e}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def run_four_chips(smoke, args):
    import jax
    import jax.numpy as jnp

    from repro.configs.dpmf import CONFIG as DPMF
    from repro.core import mf, threshold
    from repro.data.ratings import paper_dataset
    from repro.launch.mesh import make_mesh
    from repro.optim.optimizers import RowOptimizer
    from repro.serving import ServingEngine

    rng = np.random.default_rng(args.seed)
    with smoke.phase("data"):
        ds = paper_dataset("bookcrossings", seed=args.seed)
        m, n, k = ds.num_users, ds.num_items, DPMF.k
        params = mf.init_params(jax.random.PRNGKey(args.seed), m, n, k)
        t_p, t_q = threshold.thresholds_from_matrices(params.p, params.q, DPMF.pruning_rate)
        print(f"  {m} users x {n} items, k={k}, random factors from seed "
              f"{args.seed}; t_p {float(t_p):.4f} t_q {float(t_q):.4f}")

    users = np.sort(rng.choice(m, N_USERS_SERVED, replace=False)).astype(np.int32)
    with smoke.phase("sharded serving"):
        engine = ServingEngine(params, t_p, t_q)
        smoke.check("engine picks the Pallas kernel, compiled",
                    engine.use_kernel and not engine._interpret())
        want_s, want_i = engine.topk(users, TOPK)
        for shape, names in (((4,), ("model",)), ((2, 2), ("data", "model"))):
            mesh = make_mesh(shape, names)
            got_s, got_i = engine.topk_sharded(users, TOPK, mesh=mesh)
            smoke.check(f"topk_sharded on {dict(zip(names, shape))} == topk",
                        np.array_equal(got_i, want_i) and np.array_equal(got_s, want_s),
                        f"index agreement {float(np.mean(got_i == want_i)):.6f}, max "
                        f"score diff {float(np.max(np.abs(got_s - want_s))):.3e}")
        layout = engine._snap.kernel_shard_layout(2)
        print("  placement: p", params.p.sharding, "| kernel shard layout",
              [str(x.sharding) for x in layout])

    with smoke.phase("owner-compute training"):
        mesh = make_mesh((2, 2), ("data", "model"))
        steps, batch = OWNER_STEPS, OWNER_BATCH
        half = batch // 2
        # owner-compute contract: the first half of every batch holds users
        # of data shard 0 (rows [0, m/2)), the second half those of shard 1
        owner = ds.user >= m // 2
        parts = [rng.permutation(np.flatnonzero(owner == s))[: steps * half] for s in (0, 1)]
        idx = np.concatenate([p.reshape(steps, half) for p in parts], axis=1)
        batches = {
            "user": jnp.asarray(ds.user[idx]),
            "item": jnp.asarray(ds.item[idx]),
            "rating": jnp.asarray(ds.rating[idx]),
            "weight": jnp.ones((steps, batch), jnp.float32),
        }
        opt = RowOptimizer(name="adagrad")

        def fresh():
            p0 = jax.tree.map(jnp.copy, params)
            return p0, mf.init_opt_state(p0, opt)

        p0, s0 = fresh()
        ref_p, _, ref_m = mf.train_epoch_scan(
            p0, s0, batches, t_p, t_q, jnp.float32(DPMF.lr), jnp.ones((k,)),
            opt=opt, lam=DPMF.lam)
        errs = {}
        for gc in ("none", "int8_ef"):
            p0, s0 = fresh()
            with jax.sharding.set_mesh(mesh):
                if gc == "int8_ef":
                    s0 = mf.init_error_feedback_state(p0, s0, mesh)
                got_p, _, got_m = mf.train_epoch_scan_shard_map(
                    p0, s0, batches, t_p, t_q, lr=DPMF.lr, lam=DPMF.lam,
                    opt_name="adagrad", grad_compression=gc, mesh=mesh)
            dp = float(jnp.max(jnp.abs(got_p.p - ref_p.p)))
            dq = float(jnp.max(jnp.abs(got_p.q - ref_p.q)))
            errs[gc] = float(got_m["abs_err"])
            print(f"  {gc:7s}: |err| {errs[gc]:.6f} (single device "
                  f"{float(ref_m['abs_err']):.6f}); max|dp| {dp:.3e} max|dq| {dq:.3e}; "
                  f"p on {got_p.p.sharding}")
            if gc == "none":
                # duplicate rows scatter-add in an order XLA does not fix, so
                # the sharded and single-device epochs agree to float32
                # rounding compounded over the steps, not bitwise
                smoke.check("owner-compute fp32 epoch == single-device epoch",
                            dp < 1e-5 and dq < 1e-5
                            and abs(errs[gc] - float(ref_m["abs_err"])) < 1e-5)
        gap = abs(errs["int8_ef"] - errs["none"]) / errs["none"]
        smoke.check("int8_ef epoch error within 1% of fp32", gap < 0.01, f"gap {gap:.3e}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the sharded serving and owner-compute "
                             "training phases on a 4-chip host")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(REPO, ".chip_smoke"),
                        help="directory for checkpoints")
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform {platform!r} "
              f"({len(devices)} device(s)); refusing to run", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.cache import configure_compile_cache

    print(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {configure_compile_cache()}")
    smoke = Smoke(jax)
    (run_four_chips if args.four_chips else run_one_chip)(smoke, args)
    if smoke.failed:
        print(f"chip_smoke: {len(smoke.failed)} check(s) failed: {smoke.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
