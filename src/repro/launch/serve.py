"""Serving launcher: batched top-k recommendation from a trained DP-MF
checkpoint through the serving engine (``repro.serving``).

    PYTHONPATH=src python -m repro.launch.serve --ckpt /tmp/dpmf_ckpt \
        --users 0 1 2 --topk 10

The engine restores the FULL ``MFParams`` (biases and SVD++ implicit factors
included — not just ``p``/``q``), precomputes the per-item ranks and tiled
factor layout once at load, and answers requests through the streaming
pruned top-k path (Pallas kernel on TPU, ``lax.top_k``-merge scan on CPU)
without ever materializing the (B, n) score matrix.

Traffic modes on top of the one-shot lookup:

* ``--batched-requests N`` — one synchronous N-user batch (PR-1 behaviour);
* ``--concurrent N --clients C`` — N single-user requests from C client
  threads through the async request queue (``serving/queue.py``): continuous
  batching, deadline scheduling, per-request timeout; reports latency
  percentiles and throughput;
* ``--http PORT`` — a minimal event-loop server: every connection submits to
  the queue and blocks on its future, so concurrent HTTP clients coalesce
  into shared scoring launches.  ``GET /recommend?user=3&topk=10``.

``--slo-p99-ms BUDGET`` arms the SLO-aware degradation loop for
``--concurrent`` runs: an :class:`~repro.serving.slo.SLOController`
observes client latency and queue depth while the load runs and adapts the
pruning thresholds (up to ``--slo-max-rate``) to hold p99 under the
budget; the process exits non-zero if the steady-state p99 (back half of
the run) still violates it.

With ``--replicas N`` (N > 1) the same traffic modes run against a serving
*fleet* instead of a single engine: N replica engines
(``--replica-backend local`` in-process, ``process`` as spawned children)
behind the cache-aware router (``repro.serving.fleet``), so ``--http``
becomes the router's HTTP frontend and ``--concurrent`` measures routed
throughput.  ``--routing`` selects the policy (affinity/least/random).
"""
from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np

from repro.launch.cache import configure_compile_cache
from repro.serving import (
    LatencyWindow,
    QueueFullError,
    RequestTimeout,
    ServingEngine,
    SLOConfig,
    SLOController,
    load_mf_checkpoint,
)


def build_slo_controller(frontend, params, *, p99_budget_ms: float,
                         max_rate: float, tick_ms: float) -> SLOController:
    """Attach an :class:`SLOController` to either frontend kind.

    Latency is observed client-side (one shared :class:`LatencyWindow` the
    traffic loop records into), which works uniformly for a single engine
    and for process-replica fleets where the queue lives in a child."""
    config = SLOConfig(
        p99_budget_ms=p99_budget_ms,
        max_rate=max_rate,
        tick_interval_s=tick_ms / 1e3,
    )
    window = LatencyWindow()
    if isinstance(frontend, ServingEngine):
        return SLOController(
            frontend, config=config, window=window,
            depth_fn=lambda: frontend.queue_depth,
        )
    return SLOController(
        config=config, window=window, router=frontend.router,
        depth_fn=lambda: sum(r.depth() for r in frontend.router.replicas),
        params_fn=lambda: params,
    )


def _shutdown(frontend) -> None:
    """Graceful drain for either frontend kind: ``ServingEngine.stop`` or
    ``ServingFleet.close`` — both complete in-flight requests first."""
    if isinstance(frontend, ServingEngine):
        frontend.stop()
    else:
        frontend.close()


def run_concurrent(frontend, n_requests: int, clients: int,
                   topk: int, timeout: float,
                   controller: SLOController | None = None) -> dict:
    """Drive the async frontend (one engine, or a routed fleet) from
    ``clients`` submitter threads.  With a ``controller`` the loop records
    client-observed latency into its window and ticks it continuously, so
    the pruning thresholds adapt while the load runs; the returned report
    includes the controller state and the steady-state p99 (second half of
    the run, after the control loop has had time to converge)."""
    from concurrent.futures import ThreadPoolExecutor

    queue = None
    rng = np.random.default_rng(0)
    users = rng.integers(0, frontend.num_users, n_requests)
    if isinstance(frontend, ServingEngine):
        queue = frontend.start(linger_ms=1.0,
                               max_pending=max(1024, n_requests))
        # warm every power-of-two bucket a batch can land in
        for b in (1, 2, 4, 8, 16, 32, 64):
            if b <= min(frontend.max_batch, n_requests):
                frontend.topk(users[:b], topk)

    latencies = np.empty(n_requests)
    done = [0]  # completion order, distinct from submission index i
    done_lock = threading.Lock()
    order = np.empty(n_requests)

    def client(i_u):
        i, u = i_u
        t0 = time.perf_counter()
        frontend.submit(int(u), topk, timeout=timeout).result(timeout=timeout)
        dt = time.perf_counter() - t0
        latencies[i] = dt
        if controller is not None:
            controller.window.record(dt)
        with done_lock:
            order[done[0]] = dt
            done[0] += 1

    stop_tick = threading.Event()

    def ticker():
        while not stop_tick.is_set():
            controller.maybe_tick()
            stop_tick.wait(controller.config.tick_interval_s / 4)

    tick_thread = None
    if controller is not None:
        tick_thread = threading.Thread(target=ticker, daemon=True)
        tick_thread.start()

    start = time.perf_counter()
    with ThreadPoolExecutor(max_workers=clients) as pool:
        list(pool.map(client, enumerate(users)))
    wall = time.perf_counter() - start
    if tick_thread is not None:
        stop_tick.set()
        tick_thread.join(10)
    stats = None if queue is not None else frontend.stats()
    _shutdown(frontend)
    p50, p99 = np.percentile(latencies * 1e3, [50, 99])
    line = (f"concurrent: {n_requests} requests, {clients} clients in "
            f"{wall:.3f}s ({n_requests / wall:.1f} req/s; p50 {p50:.2f} ms, "
            f"p99 {p99:.2f} ms")
    if queue is not None:
        line += (f"; {queue.batches_served} launches, mean batch "
                 f"{queue.requests_served / queue.batches_served:.1f})")
    else:
        line += (f"; routed over {len(stats['replicas'])} replicas, "
                 f"policy={stats['policy']}, "
                 f"affinity hits {stats['affinity_hits']})")
    print(line)

    report = {
        "requests": n_requests,
        "wall_s": wall,
        "req_per_s": n_requests / wall,
        "p50_ms": float(p50),
        "p99_ms": float(p99),
    }
    if controller is not None:
        # judge the SLO on the back half of completions: the front half is
        # the controller still hunting for an operating point
        steady = order[n_requests // 2:done[0]]
        steady_p99 = (
            float(np.percentile(steady * 1e3, 99)) if steady.size
            else float("nan")
        )
        report["slo"] = controller.report()
        report["steady_p99_ms"] = steady_p99
        report["slo_violated"] = bool(
            np.isfinite(steady_p99)
            and steady_p99 > controller.config.p99_budget_ms
        )
        print(f"slo: steady-state p99 {steady_p99:.2f} ms vs budget "
              f"{controller.config.p99_budget_ms:.2f} ms "
              f"({'VIOLATED' if report['slo_violated'] else 'ok'}); "
              f"rate {report['slo']['applied_rate']}, "
              f"{report['slo']['degrades']} degrades / "
              f"{report['slo']['relaxes']} relaxes over "
              f"{report['slo']['ticks']} ticks")
    return report


def run_http(frontend, port: int, topk_default: int,
             timeout: float) -> None:
    """Blocking HTTP front end over the async queue — or, for a fleet, over
    the router (stdlib only).  Shutdown drains: in-flight requests complete
    before the process exits."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    from urllib.parse import parse_qs, urlparse

    if isinstance(frontend, ServingEngine):
        frontend.start(linger_ms=1.0)

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # quiet access log
            pass

        def _reply(self, status: int, payload: dict) -> None:
            body = json.dumps(payload).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            url = urlparse(self.path)
            if url.path != "/recommend":
                return self._reply(404, {"error": "GET /recommend?user=..."})
            qs = parse_qs(url.query)
            try:
                user = int(qs["user"][0])
                topk = int(qs.get("topk", [topk_default])[0])
                scores, items = frontend.submit(
                    user, topk, timeout=timeout
                ).result(timeout=timeout)
            except (KeyError, ValueError, IndexError) as exc:
                return self._reply(400, {"error": str(exc)})
            except QueueFullError as exc:
                return self._reply(503, {"error": str(exc)})
            except (RequestTimeout, TimeoutError) as exc:
                return self._reply(504, {"error": str(exc)})
            self._reply(200, {
                "user": user,
                "items": [
                    {"item": int(i), "score": round(float(s), 4)}
                    for i, s in zip(items, scores)
                ],
            })

    server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    print(f"# serving http://127.0.0.1:{port}/recommend?user=0&topk="
          f"{topk_default} (Ctrl-C to stop)")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        _shutdown(frontend)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--ckpt", required=True)
    parser.add_argument("--users", type=int, nargs="+", default=[0])
    parser.add_argument("--topk", type=int, default=10)
    parser.add_argument("--batched-requests", type=int, default=0,
                        help="simulate N random-user requests and report latency")
    parser.add_argument("--concurrent", type=int, default=0,
                        help="simulate N single-user requests through the "
                             "async queue")
    parser.add_argument("--clients", type=int, default=32,
                        help="submitter threads for --concurrent")
    parser.add_argument("--timeout", type=float, default=30.0,
                        help="per-request timeout (seconds) for async modes")
    parser.add_argument("--http", type=int, default=0, metavar="PORT",
                        help="serve GET /recommend over HTTP on PORT")
    parser.add_argument("--max-batch", type=int, default=256,
                        help="micro-batch bucket cap")
    parser.add_argument("--use-kernel", action="store_true",
                        help="force the Pallas kernel path (default: TPU only)")
    parser.add_argument("--history", default=None,
                        help="(.npy) padded per-user item-history matrix for "
                             "SVD++ checkpoints (see data.build_user_history)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="serve through a fleet of N replica engines "
                             "behind the cache-aware router (1 = single "
                             "engine, the classic path)")
    parser.add_argument("--replica-backend", choices=("local", "process"),
                        default="local",
                        help="fleet replicas in-process or as spawned "
                             "multiprocessing children (CPU only: a TPU "
                             "chip belongs to one process)")
    parser.add_argument("--routing", choices=("affinity", "least", "random"),
                        default="affinity",
                        help="fleet routing policy (see serving/fleet/router)")
    parser.add_argument("--slo-p99-ms", type=float, default=0.0,
                        help="enable the SLO controller with this p99 "
                             "latency budget (ms) for --concurrent; the "
                             "process exits non-zero if the steady-state "
                             "p99 still violates the budget (0 = off)")
    parser.add_argument("--slo-max-rate", type=float, default=0.8,
                        help="ceiling on the controller's effective pruning "
                             "rate (the quality floor)")
    parser.add_argument("--slo-tick-ms", type=float, default=100.0,
                        help="controller tick interval (ms)")
    args = parser.parse_args()
    configure_compile_cache()

    params, t_p, t_q, _, meta = load_mf_checkpoint(args.ckpt)
    user_history = None if args.history is None else np.load(args.history)
    if params.implicit is not None and user_history is None:
        print("# warning: SVD++ checkpoint served without --history — "
              "implicit factors contribute nothing (user vectors fall back "
              "to p alone)")
    engine_kwargs = dict(
        max_batch=args.max_batch,
        use_kernel=True if args.use_kernel else None,
        allow_missing_history=True,
    )
    engine = ServingEngine(
        params, t_p, t_q, user_history=user_history, **engine_kwargs
    )
    variant = (
        "svdpp" if params.implicit is not None
        else "bias" if params.user_bias is not None
        else "funk"
    )
    print(f"# loaded step {meta.get('step')} variant={variant} "
          f"({engine.num_users} users x {engine.n_items} items, k={engine.k})")

    frontend = engine
    if args.replicas > 1:
        from repro.serving.fleet import ServingFleet

        frontend = ServingFleet(
            params, t_p, t_q,
            replicas=args.replicas,
            backend=args.replica_backend,
            user_history=user_history,
            engine_kwargs=engine_kwargs,
            queue_kwargs={"linger_ms": 1.0},
            router_kwargs={"policy": args.routing},
        )
        print(f"# fleet: {args.replicas} {args.replica_backend} replicas, "
              f"routing={args.routing}")

    if args.http:
        return run_http(frontend, args.http, args.topk, args.timeout)

    recs = engine.recommend(args.users, topk=args.topk)
    print(json.dumps({str(u): r for u, r in zip(args.users, recs)}, indent=2))

    if args.batched_requests:
        rng = np.random.default_rng(0)
        users = rng.integers(0, engine.num_users, args.batched_requests)
        # warm every bucket the request mix hits (incl. the tail chunk's), so
        # no compile lands inside the timed region
        engine.topk(users, args.topk)
        start = time.perf_counter()
        engine.topk(users, args.topk)
        dt = time.perf_counter() - start
        print(f"batched: {args.batched_requests} requests in {dt:.3f}s "
              f"({args.batched_requests / dt:.1f} req/s)")

    if args.concurrent:
        controller = None
        if args.slo_p99_ms > 0:
            controller = build_slo_controller(
                frontend, params,
                p99_budget_ms=args.slo_p99_ms,
                max_rate=args.slo_max_rate,
                tick_ms=args.slo_tick_ms,
            )
            print(f"# slo: p99 budget {args.slo_p99_ms} ms, floor rate "
                  f"{controller.floor_rate:.3f}, max rate "
                  f"{args.slo_max_rate}")
        report = run_concurrent(frontend, args.concurrent, args.clients,
                                args.topk, args.timeout,
                                controller=controller)
        if report.get("slo_violated"):
            raise SystemExit(
                f"SLO violated: steady-state p99 "
                f"{report['steady_p99_ms']:.2f} ms > budget "
                f"{args.slo_p99_ms:.2f} ms"
            )
    elif frontend is not engine:
        frontend.close()


if __name__ == "__main__":
    main()
