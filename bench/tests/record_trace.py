#!/usr/bin/env python3
"""Record ``data/small.xplane.pb``, the trace ``test_trace.py`` reduces.

Run on a TPU host from the checkout's root:

    python3 bench/tests/record_trace.py <output directory>

Inside a ``bench.window`` span it runs a short training epoch scan, a
``ServingEngine.topk`` call on the Pallas kernel, and a 100 ms host sleep
in a ``bench.idle`` span, which leaves the device idle for that long.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import mf
    from repro.core.mf import MFParams
    from repro.optim.optimizers import RowOptimizer
    from repro.serving.engine import ServingEngine

    key = jax.random.PRNGKey(0)
    p = 0.1 * jax.random.normal(key, (1024, 128))
    q = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (4096, 128))
    params = MFParams(p, q, None, None, None, None)
    opt = RowOptimizer("adagrad")
    batches = {"user": jnp.arange(8 * 256, dtype=jnp.int32).reshape(8, 256) % 1024,
               "item": jnp.arange(8 * 256, dtype=jnp.int32).reshape(8, 256) % 4096,
               "rating": jnp.ones((8, 256), jnp.float32)}
    engine = ServingEngine(jax.tree_util.tree_map(jnp.copy, params), 0.05, 0.05)
    ones = jnp.ones((128,), jnp.float32)

    def epoch(prm, st):
        return mf.train_epoch_scan(prm, st, batches, jnp.float32(0.05), jnp.float32(0.05),
                                   jnp.float32(0.05), ones, opt=opt, lam=0.02)

    state = mf.init_opt_state(params, opt)
    params, state, _ = epoch(params, state)          # warm-up
    engine.topk(np.arange(256, dtype=np.int32), 10)
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.epoch"):
            params, state, m = epoch(params, state)
            jax.block_until_ready(m)
        with jax.profiler.TraceAnnotation("bench.idle"):
            time.sleep(0.1)
        with jax.profiler.TraceAnnotation("bench.topk_call"):
            engine.topk(np.arange(256, dtype=np.int32), 10)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(sys.argv[1], exist_ok=True)
    shutil.copy(path, os.path.join(sys.argv[1], "small.xplane.pb"))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
