"""Fleet replicas: one serving engine + queue + version gate per replica.

Two implementations of the same contract (``replica_id``, ``version``,
``submit() -> Future``, ``apply_update(msg) -> ack``, ``depth()``,
``stats()``, ``close()``):

* :class:`LocalReplica` — everything in this process.  What CI exercises
  for the replication/ routing logic, what the benches use to measure
  routing policies without IPC noise, and the building block the process
  replica runs inside its child.

* :class:`ProcessReplica` — a ``multiprocessing`` (spawn) child running a
  ``LocalReplica``, talked to over a duplex pipe.  Spawn (not fork) so the
  child re-imports cleanly next to JAX's threadpools — the only mode safe
  on CPU CI.  CPU only: on a TPU host the chip belongs to the parent
  process, so a child could never reach it — construction refuses at once
  there instead of hanging until ``start_timeout``.  The child bootstraps either from a ``kind=full``
  :class:`~repro.serving.fleet.bus.DeltaMessage` or from a checkpoint
  directory (training base + ``fold_deltas`` over the online delta chain —
  the late-join path, which leaves the replica at the chain's last version
  so the live bus can resume with deltas).

Requests return ``concurrent.futures.Future`` either way; for process
replicas a reader thread resolves them from pipe replies, so the router
never blocks on a slow replica.
"""
from __future__ import annotations

import multiprocessing as mp
import threading
from concurrent.futures import Future
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.serving.fleet import bus
from repro.testing import faults


class ReplicaDiedError(RuntimeError):
    """The replica behind a request/control call is dead.

    Raised fast by ``submit`` once death is known (no writing into a broken
    pipe, no waiting out a timeout), and set on every future that was still
    pending when the pipe broke — the router catches exactly this type to
    fail over to a healthy replica, and the supervisor to trigger respawn.
    """


class LocalReplica:
    """One in-process replica: engine + started request queue + gated sink."""

    def __init__(
        self,
        replica_id: str,
        params,
        t_p=0.0,
        t_q=0.0,
        *,
        user_history: Optional[np.ndarray] = None,
        base_version: int = 0,
        engine_kwargs: Optional[dict] = None,
        queue_kwargs: Optional[dict] = None,
    ):
        from repro.serving.engine import ServingEngine

        self.replica_id = replica_id
        self.engine = ServingEngine(
            params, t_p, t_q, user_history=user_history, **(engine_kwargs or {})
        )
        self.queue = self.engine.start(**(queue_kwargs or {}))
        self._sink = bus.EngineDeltaSink(
            self.engine,
            user_history=user_history,
            version=base_version,
            replica_id=replica_id,
        )
        self._dead = False

    @property
    def version(self) -> int:
        """Replication version this replica currently serves."""
        return self._sink.version

    @property
    def num_users(self) -> int:
        """User-table rows of the served snapshot."""
        return self.engine.num_users

    @property
    def alive(self) -> bool:
        """Liveness flag — the supervisor's health probe for in-process
        replicas (a thread can't vanish the way a child process can, so a
        local replica only dies via :meth:`kill`)."""
        return not self._dead

    def ping(self, timeout: float = 5.0) -> bool:
        """Heartbeat probe: True iff the replica would serve a request."""
        return not self._dead

    def kill(self) -> None:
        """Simulated crash (chaos harness): every queued request fails with
        :class:`ReplicaDiedError` immediately, later submits raise fast —
        the in-process twin of ``ProcessReplica``'s child dying."""
        if self._dead:
            return
        self._dead = True
        self.queue.abort(
            ReplicaDiedError(f"replica {self.replica_id} died (injected)")
        )

    def submit(self, user_id: int, topk: int = 10, *, timeout=None,
               priority: int = 0) -> Future:
        """Enqueue one request on this replica's queue.

        Raises :class:`ReplicaDiedError` immediately once the replica is
        dead — callers (the router) fail over instead of queueing into a
        corpse."""
        if faults._PLAN is not None:
            for act in faults.fire("replica.submit", self.replica_id):
                if act.op == "kill":
                    self.kill()
        if self._dead:
            raise ReplicaDiedError(f"replica {self.replica_id} is dead")
        return self.engine.submit(user_id, topk, timeout=timeout,
                                  priority=priority)

    def apply_update(self, msg: bus.DeltaMessage) -> int:
        """Offer a bus message to the version gate; returns the ack.

        The hot swap happens under live traffic: requests in flight finish
        on the old snapshot, the queue never pauses."""
        if self._dead:
            raise ReplicaDiedError(f"replica {self.replica_id} is dead")
        return self._sink.apply_update(msg)

    def state_message(self) -> bus.DeltaMessage:
        """Full served state as a ``kind=full`` message — what the
        supervisor pulls from a healthy peer to heal a respawn."""
        return self._sink.state_message()

    def set_thresholds(self, t_p, t_q) -> int:
        """Pin SLO serving thresholds on this replica (see
        :meth:`~repro.serving.fleet.bus.EngineDeltaSink.set_thresholds`)."""
        return self._sink.set_thresholds(t_p, t_q)

    def depth(self) -> int:
        """Queued + in-scoring requests — the router's load signal."""
        return self.engine.queue_depth

    def stats(self) -> Dict[str, Any]:
        """Counters for benches/CI: version, load, cache hit rate, queue."""
        cache = self.engine.vector_cache
        gate = self._sink.gate
        return {
            "replica_id": self.replica_id,
            "version": self.version,
            "depth": self.depth(),
            "num_users": self.engine.num_users,
            "n_items": self.engine.n_items,
            "requests_served": self.queue.requests_served,
            "batches_served": self.queue.batches_served,
            "expired": self.queue.expired,
            "cache_hits": cache.hits,
            "cache_misses": cache.misses,
            "updates_applied": gate.applied,
            "updates_duplicate": gate.duplicates,
            "updates_buffered": gate.buffered,
            "updates_corrupt": self._sink.corrupt_dropped,
        }

    def close(self) -> None:
        """Drain the queue (every accepted request completes) and stop."""
        self.engine.stop()


# ---------------------------------------------------------------------------
# Process replicas
# ---------------------------------------------------------------------------


def _child_bootstrap(init: dict):
    """Build the child's initial ``(params, t_p, t_q, history, version)``
    from the spawn payload: a full message, or checkpoint dirs to fold."""
    if "msg" in init:
        m = init["msg"]
        params, t_p, t_q, history = bus.state_from_message(m)
        return params, t_p, t_q, history, int(m.version)
    from repro.online.publisher import fold_deltas
    from repro.serving.engine import load_mf_checkpoint

    params, t_p, t_q, _, _ = load_mf_checkpoint(init["checkpoint"])
    version = 0
    history = None
    if init.get("online_dir"):
        params, t_p, t_q, history, version = fold_deltas(
            init["online_dir"], params, t_p, t_q
        )
    return params, t_p, t_q, history, version


def _replica_main(conn, replica_id: str, init: dict,
                  engine_kwargs: Optional[dict],
                  queue_kwargs: Optional[dict]) -> None:
    """Child process entry: run a :class:`LocalReplica`, serve the pipe.

    Protocol (parent -> child): ``("submit", rid, user, topk, timeout,
    priority)``, ``("update", msg)``, ``("thresholds", t_p, t_q)``,
    ``("stats",)``, ``("ping", seq)``, ``("state",)``, ``("close",)``.
    Child -> parent: ``("ready", version, num_users)``, ``("result", rid,
    scores, items)``, ``("error", rid, repr)``, ``("ack", version, ack)``,
    ``("tack", ack)``, ``("stats", dict)``, ``("pong", seq)``,
    ``("state_msg", DeltaMessage)``, ``("bye",)``.
    """
    send_lock = threading.Lock()

    def send(*payload):
        with send_lock:  # queue scheduler + pipe loop both reply
            try:
                conn.send(payload)
            except (BrokenPipeError, OSError):
                pass

    try:
        params, t_p, t_q, history, version = _child_bootstrap(init)
        replica = LocalReplica(
            replica_id, params, t_p, t_q,
            user_history=history, base_version=version,
            engine_kwargs=engine_kwargs, queue_kwargs=queue_kwargs,
        )
    except Exception as exc:  # surface the spawn failure to the parent
        send("error", -1, f"{type(exc).__name__}: {exc}")
        conn.close()
        return
    send("ready", replica.version, replica.num_users)

    def reply(rid: int, fut: Future) -> None:
        try:
            scores, items = fut.result()
            send("result", rid, np.asarray(scores), np.asarray(items))
        except Exception as exc:
            send("error", rid, f"{type(exc).__name__}: {exc}")

    try:
        while True:
            try:
                op, *rest = conn.recv()
            except (EOFError, OSError):
                break
            if op == "submit":
                rid, user, topk, timeout, priority = rest
                try:
                    fut = replica.submit(int(user), int(topk),
                                         timeout=timeout, priority=priority)
                except Exception as exc:
                    send("error", rid, f"{type(exc).__name__}: {exc}")
                else:
                    fut.add_done_callback(
                        lambda f, rid=rid: reply(rid, f)
                    )
            elif op == "update":
                (msg,) = rest
                try:
                    ack = replica.apply_update(msg)
                except Exception as exc:
                    send("error", -1, f"{type(exc).__name__}: {exc}")
                else:
                    send("ack", msg.version, ack)
            elif op == "thresholds":
                tp, tq = rest
                try:
                    ack = replica.set_thresholds(tp, tq)
                except Exception as exc:
                    send("error", -1, f"{type(exc).__name__}: {exc}")
                else:
                    send("tack", ack)
            elif op == "stats":
                send("stats", replica.stats())
            elif op == "ping":
                # heartbeat: answered from the pipe loop, so a wedged pipe
                # loop (or dead process) reads as probe timeout upstream
                send("pong", *rest)
            elif op == "state":
                try:
                    send("state_msg", replica.state_message())
                except Exception as exc:
                    send("error", -1, f"{type(exc).__name__}: {exc}")
            elif op == "close":
                replica.close()  # drains: every queued future resolves+sends
                send("bye")
                break
    finally:
        conn.close()


class ProcessReplica:
    """Parent-side handle to a replica running in a spawned child process.

    Bootstrap with either ``init_msg`` (a ``kind=full``
    :class:`~repro.serving.fleet.bus.DeltaMessage`; build one with
    ``bus.state_message``) or ``checkpoint=...`` (+ optional
    ``online_dir=...`` to fold the delta chain — the late-join catch-up).
    ``submit`` returns a local Future resolved by the reader thread;
    ``apply_update`` blocks for the child's ack (the publisher's rolling
    fan-out needs the ack before moving to the next replica).
    """

    def __init__(
        self,
        replica_id: str,
        *,
        init_msg: Optional[bus.DeltaMessage] = None,
        checkpoint: Optional[str] = None,
        online_dir: Optional[str] = None,
        engine_kwargs: Optional[dict] = None,
        queue_kwargs: Optional[dict] = None,
        start_timeout: float = 180.0,
    ):
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "process replicas cannot run on a TPU host: a chip belongs to "
                "one process and this one already holds it, so a spawned "
                "replica would fail or hang reaching for it; use "
                "backend='local' (in-process replicas) instead"
            )
        if (init_msg is None) == (checkpoint is None):
            raise ValueError("pass exactly one of init_msg / checkpoint")
        init = {"msg": init_msg} if init_msg is not None else {
            "checkpoint": checkpoint, "online_dir": online_dir,
        }
        self.replica_id = replica_id
        # everything needed to spawn an equivalent replacement — the
        # supervisor's respawn spec (it overrides the boot state itself)
        self.spawn_kwargs = {
            "checkpoint": checkpoint, "online_dir": online_dir,
            "engine_kwargs": engine_kwargs, "queue_kwargs": queue_kwargs,
            "start_timeout": start_timeout,
        }
        ctx = mp.get_context("spawn")
        self._conn, child_conn = ctx.Pipe()
        self._proc = ctx.Process(
            target=_replica_main,
            args=(child_conn, replica_id, init, engine_kwargs, queue_kwargs),
            daemon=True,
        )
        self._proc.start()
        child_conn.close()
        self._lock = threading.Lock()          # pipe writes
        self._futs: Dict[int, Future] = {}
        self._futs_lock = threading.Lock()
        self._next_rid = 0
        self._acks: Dict[int, int] = {}
        self._ack_event = threading.Condition()
        self._stats: Optional[dict] = None
        self._stats_event = threading.Event()
        self._tack: Optional[int] = None
        self._tack_event = threading.Event()
        self._pongs: set = set()
        self._pong_event = threading.Condition()
        self._ping_seq = 0
        self._state_msg: Optional[bus.DeltaMessage] = None
        self._state_event = threading.Event()
        self._ready = threading.Event()
        self._bye = threading.Event()
        self._dead = threading.Event()
        self.version = 0
        self.num_users = 0
        self._spawn_error: Optional[str] = None
        self._reader = threading.Thread(
            target=self._read_loop, name=f"fleet-{replica_id}-reader",
            daemon=True,
        )
        self._reader.start()
        if not self._ready.wait(start_timeout):
            self._proc.terminate()
            raise TimeoutError(f"replica {replica_id} did not come up")
        if self._spawn_error is not None:
            raise RuntimeError(
                f"replica {replica_id} failed to start: {self._spawn_error}"
            )

    def _read_loop(self) -> None:
        while True:
            try:
                op, *rest = self._conn.recv()
            except (EOFError, OSError):
                break
            if op == "ready":
                self.version, self.num_users = rest
                self._ready.set()
            elif op == "result":
                rid, scores, items = rest
                fut = self._pop_fut(rid)
                if fut is not None:
                    fut.set_result((scores, items))
            elif op == "error":
                rid, text = rest
                if rid == -1 and not self._ready.is_set():
                    self._spawn_error = text
                    self._ready.set()
                    continue
                fut = self._pop_fut(rid)
                if fut is not None:
                    fut.set_exception(RuntimeError(text))
            elif op == "ack":
                version, ack = rest
                with self._ack_event:
                    self._acks[version] = ack
                    self._ack_event.notify_all()
            elif op == "tack":
                (self._tack,) = rest
                self._tack_event.set()
            elif op == "stats":
                (self._stats,) = rest
                self._stats_event.set()
            elif op == "pong":
                (seq,) = rest
                with self._pong_event:
                    self._pongs.add(seq)
                    self._pong_event.notify_all()
            elif op == "state_msg":
                (self._state_msg,) = rest
                self._state_event.set()
            elif op == "bye":
                self._bye.set()
        # Pipe gone: the child died (or closed).  Mark death FIRST so new
        # submits raise fast, then fail everything outstanding — futures,
        # ack/pong/stats waiters, even a constructor still waiting on
        # "ready" (a child that crashes during bootstrap must not cost the
        # caller the full start timeout).
        self._dead.set()
        if not self._ready.is_set():
            if self._spawn_error is None:
                self._spawn_error = "process exited during bootstrap"
            self._ready.set()
        with self._futs_lock:
            leftovers, self._futs = list(self._futs.values()), {}
        exc = ReplicaDiedError(
            f"replica {self.replica_id} died (pipe closed, "
            f"exitcode={self._proc.exitcode})"
        )
        for fut in leftovers:
            if not fut.done():
                fut.set_exception(exc)
        with self._ack_event:
            self._ack_event.notify_all()
        with self._pong_event:
            self._pong_event.notify_all()
        self._tack_event.set()
        self._stats_event.set()
        self._state_event.set()
        self._bye.set()

    def _pop_fut(self, rid: int) -> Optional[Future]:
        with self._futs_lock:
            return self._futs.pop(rid, None)

    def _send(self, *payload) -> None:
        with self._lock:
            self._conn.send(payload)

    @property
    def alive(self) -> bool:
        """False once the child died or its pipe broke — the supervisor's
        cheap (no round-trip) liveness signal."""
        return not self._dead.is_set() and self._proc.is_alive()

    @property
    def exitcode(self) -> Optional[int]:
        """The child's exit code (None while running) — nonzero after a
        crash/kill, part of the supervisor's death evidence."""
        return self._proc.exitcode

    def kill(self) -> None:
        """Hard-kill the child (SIGKILL) — the chaos harness's process
        death.  The reader thread observes the pipe EOF and fails every
        outstanding future with :class:`ReplicaDiedError`."""
        self._proc.kill()

    def ping(self, timeout: float = 5.0) -> bool:
        """Round-trip heartbeat through the child's pipe loop.  False on
        timeout, dead child, or broken pipe — never raises: this is the
        probe the supervisor calls on every tick."""
        if self._dead.is_set():
            return False
        with self._pong_event:
            seq = self._ping_seq
            self._ping_seq += 1
        try:
            self._send("ping", seq)
        except (BrokenPipeError, OSError, ReplicaDiedError):
            return False
        with self._pong_event:
            self._pong_event.wait_for(
                lambda: seq in self._pongs or self._dead.is_set(), timeout
            )
            got = seq in self._pongs
            self._pongs.discard(seq)
        return got

    def _raise_if_dead(self) -> None:
        if self._dead.is_set():
            raise ReplicaDiedError(
                f"replica {self.replica_id} is dead "
                f"(exitcode={self._proc.exitcode})"
            )

    def submit(self, user_id: int, topk: int = 10, *, timeout=None,
               priority: int = 0) -> Future:
        """Forward one request to the child; the reader thread resolves the
        returned Future from the pipe reply.  Raises
        :class:`ReplicaDiedError` fast once the child is dead (never writes
        into a broken pipe, never strands a future)."""
        if faults._PLAN is not None:
            for act in faults.fire("replica.submit", self.replica_id):
                if act.op == "kill":
                    self.kill()
        self._raise_if_dead()
        fut: Future = Future()
        with self._futs_lock:
            rid = self._next_rid
            self._next_rid += 1
            self._futs[rid] = fut
        try:
            self._send("submit", rid, int(user_id), int(topk), timeout,
                       int(priority))
        except (BrokenPipeError, OSError):
            # lost the race with death: behave exactly like a fast-raise
            self._pop_fut(rid)
            raise ReplicaDiedError(
                f"replica {self.replica_id} died (pipe write failed)"
            ) from None
        return fut

    def apply_update(self, msg: bus.DeltaMessage, *, timeout: float = 180.0) -> int:
        """Ship a bus message and block for the child's ack (its version
        after gating) — the rolling fan-out's synchronization point."""
        self._raise_if_dead()
        try:
            self._send("update", msg)
        except (BrokenPipeError, OSError):
            raise ReplicaDiedError(
                f"replica {self.replica_id} died (pipe write failed)"
            ) from None
        with self._ack_event:
            if not self._ack_event.wait_for(
                lambda: msg.version in self._acks or self._dead.is_set(),
                timeout,
            ):
                raise TimeoutError(
                    f"replica {self.replica_id}: no ack for v{msg.version}"
                )
            if msg.version not in self._acks:
                self._raise_if_dead()
            ack = self._acks.pop(msg.version)
        self.version = max(self.version, ack)
        return ack

    def state_message(self, *, timeout: float = 180.0) -> bus.DeltaMessage:
        """Fetch the child's full served state as a ``kind=full`` message —
        the peer-heal payload the supervisor replicates into a respawn."""
        self._raise_if_dead()
        self._state_event.clear()
        self._state_msg = None
        self._send("state")
        if not self._state_event.wait(timeout):
            raise TimeoutError(f"replica {self.replica_id}: state timed out")
        if self._state_msg is None:
            self._raise_if_dead()
            raise RuntimeError(f"replica {self.replica_id}: state fetch failed")
        return self._state_msg

    def set_thresholds(self, t_p, t_q, *, timeout: float = 120.0) -> int:
        """Pin SLO serving thresholds in the child and block for its ack —
        same synchronization discipline as :meth:`apply_update` (the
        rolling rollout must not move on before the swap lands)."""
        self._raise_if_dead()
        self._tack_event.clear()
        self._tack = None
        tp = None if t_p is None else float(t_p)
        tq = None if t_q is None else float(t_q)
        self._send("thresholds", tp, tq)
        if not self._tack_event.wait(timeout):
            raise TimeoutError(
                f"replica {self.replica_id}: threshold swap not acked"
            )
        if self._tack is None:
            self._raise_if_dead()
            raise RuntimeError(f"replica {self.replica_id}: no threshold ack")
        return int(self._tack)

    def depth(self) -> int:
        """Requests submitted here and not yet resolved — the parent-side
        load proxy (no pipe round-trip, so the router can poll it hot)."""
        with self._futs_lock:
            return len(self._futs)

    def stats(self, *, timeout: float = 60.0) -> Dict[str, Any]:
        """Fetch the child's counter snapshot over the pipe."""
        self._raise_if_dead()
        self._stats_event.clear()
        self._stats = None
        self._send("stats")
        if not self._stats_event.wait(timeout):
            raise TimeoutError(f"replica {self.replica_id}: stats timed out")
        if self._stats is None:
            self._raise_if_dead()
            raise RuntimeError(f"replica {self.replica_id}: no stats reply")
        return dict(self._stats)

    def close(self, *, timeout: float = 120.0) -> None:
        """Drain the child (in-flight requests complete and their results
        flow back), then join the process."""
        try:
            self._send("close")
        except (BrokenPipeError, OSError):
            pass
        self._bye.wait(timeout)
        self._proc.join(timeout)
        if self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(10)
        try:
            self._conn.close()
        except OSError:
            pass
