"""Batched top-k recommendation engine over a trained DP-MF checkpoint.

Replaces the score-everything-then-argsort serve path.  The old path
materialized a (B, n) score matrix in HBM and argsorted the full catalog per
request — exactly the "unnecessary operations" the paper prunes, and the
memory-bound pattern GPU-MF studies identify at catalog scale.  The engine:

* **loads once, serves many** — per-item effective ranks ``r_i``, the masked
  (rank-truncated) item factors, item biases, and the kernel's padded/tiled
  layouts are all computed at load time, not per request;
* **never materializes (B, n)** — scoring streams over item tiles keeping a
  running per-user top-k: the Pallas fused pruned-score+top-k kernel on TPU
  (``kernels/pruned_topk.py``), a ``lax.top_k``-merge scan on CPU;
* **micro-batches** — request batches are padded to power-of-two buckets so
  the jit cache stays bounded (``serving/batching.py``);
* **caches hot users** — computed user vectors (the SVD++ history
  aggregation in particular) go through an LRU;
* **shards both operand axes** — ``topk_sharded`` scores per-shard top-k
  under ``shard_map`` with item tiles over the "model" mesh axis and user
  rows over the data axes (2-D when the mesh has both), cross-merging the
  shard winners, so one engine spans item tables bigger than one device
  *and* fans request batches out across the user axis;
* **pipelines requests** — ``submit()`` hands a request to the continuous
  batching queue (``serving/queue.py``) and returns a future; concurrent
  callers coalesce into deadline-ordered batches instead of serializing
  full scoring launches;
* **hot-swaps factor versions** — :meth:`swap` publishes a new
  ``(params, t_p, t_q)`` snapshot without dropping requests.  All
  model-derived state (factors, ranks, tiled layouts, user constants, the
  hot-user LRU) lives in an immutable per-version :class:`_Snapshot`; every
  scoring batch captures the current snapshot ONCE at entry, so a concurrent
  swap never changes results mid-batch and each result is deterministic for
  the version that served it.  Swaps are double-buffered: the next version's
  layouts are built (incrementally, for touched item rows only, when the
  thresholds and catalog geometry are unchanged) before the atomic flip.

Scores returned are full model scores (user/global biases folded back in
after ranking — per-user constants never change the ranking itself).
"""
from __future__ import annotations

import threading
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.checkpoint import checkpoint as ckpt_lib
from repro.core import mf
from repro.core.ranks import effective_ranks, rank_mask
from repro.kernels.ops import (
    TOPK_BLOCK_K,
    TOPK_BLOCK_N,
    pad_catalog_for_topk_kernel,
    pad_users_for_topk_kernel,
    stream_topk_tiles,
    tile_catalog,
)
from repro.kernels.pruned_topk import pruned_topk_padded
from repro.serving.batching import LRUCache, bucket_size

_NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# Checkpoint loading (full MFParams — biases and implicit factors included)
# ---------------------------------------------------------------------------


def load_mf_checkpoint(
    directory: str, *, step: Optional[int] = None
) -> Tuple[mf.MFParams, jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray], dict]:
    """Load a DP-MF trainer checkpoint for serving.

    Restores the FULL ``MFParams`` — ``p``/``q`` plus user/item biases,
    global mean, and SVD++ implicit factors when the checkpoint has them
    (the old serve loader dropped everything but ``p``/``q``, silently
    serving wrong scores for BiasSVD/SVD++ checkpoints).  Returns
    ``(params, t_p, t_q, perm, metadata)``.
    """
    data, meta = ckpt_lib.load_raw(directory, step)
    params = mf.params_from_flat(data)

    def opt(key):
        return jnp.asarray(data[key]) if key in data else None

    t_p = opt("t_p")
    t_q = opt("t_q")
    perm = opt("perm")
    t_p = jnp.float32(0.0) if t_p is None else t_p.astype(jnp.float32)
    t_q = jnp.float32(0.0) if t_q is None else t_q.astype(jnp.float32)
    return params, t_p, t_q, perm, meta


# ---------------------------------------------------------------------------
# Versioned model snapshots
# ---------------------------------------------------------------------------


class _Snapshot:
    """One immutable factor version plus everything derived from it.

    Scoring entry points capture ``engine._snap`` exactly once per request
    batch and thread it through the whole launch, so :meth:`ServingEngine.swap`
    (a plain attribute store, atomic under the GIL) can flip versions while
    requests are in flight: a batch that started on version v finishes on
    version v, bit-for-bit.  Layouts are built lazily under ``_build_lock``
    and reused (or incrementally patched) across swaps.
    """

    def __init__(
        self,
        version: int,
        params: mf.MFParams,
        t_p,
        t_q,
        *,
        block_n: int,
        cache: LRUCache,
        user_history: Optional[np.ndarray],
        r_i: Optional[jnp.ndarray] = None,
        user_const: Optional[np.ndarray] = None,
        compact_latent: bool = False,
        user_remap: Optional[np.ndarray] = None,
        remap_epoch: int = 0,
    ):
        self.version = version
        self.params = params
        self.t_p = jnp.asarray(t_p, jnp.float32)
        self.t_q = jnp.asarray(t_q, jnp.float32)
        self.num_users, self.k = params.p.shape
        self.n_items = params.q.shape[0]
        self.block_n = block_n
        self.cache = cache
        self.user_history = user_history
        self.compact_latent = compact_latent
        # Cold-row eviction (store/eviction.py): request ids are *external*;
        # ``user_remap[ext] -> physical row or -1 (spilled)``.  Without an
        # evictor upstream the remap is None and ids are physical as before.
        self.user_remap = (
            None if user_remap is None else np.asarray(user_remap, np.int32)
        )
        self.remap_epoch = int(remap_epoch)
        self.num_external = (
            self.num_users if self.user_remap is None
            else int(self.user_remap.shape[0])
        )
        self._fallback_topk = {}  # topk -> (scores, idx) for spilled users

        # ``r_i``/``user_const`` accept precomputed values so an incremental
        # swap can patch the previous snapshot's at the touched rows instead
        # of re-reducing the full catalog / user table
        self.r_i = (
            effective_ranks(params.q, self.t_q) if r_i is None else r_i
        )
        self.item_bias_vec = (
            params.item_bias[:, 0].astype(jnp.float32)
            if params.item_bias is not None
            else jnp.zeros((self.n_items,), jnp.float32)
        )
        # per-user additive constant (never changes ranking; folded back in
        # after top-k so returned scores equal full model scores); host-side
        # because it is applied to host result arrays per request
        if user_const is not None:
            self.user_const = user_const
        elif params.user_bias is not None:
            self.user_const = np.asarray(
                params.user_bias[:, 0].astype(jnp.float32) + params.global_mean
            )
        else:
            self.user_const = None

        # Scoring layouts are built lazily on first use so a snapshot only
        # holds the catalog copies its configured path actually reads:
        # streaming tiles (rank-masked f32), or the kernel's padded raw
        # factors + ranks (it re-masks per K-block so it can skip K-blocks).
        self._stream_layout = None
        self._kernel_layout = None
        self._shard_layouts = {}
        self._kernel_shard_layouts = {}
        self._build_lock = threading.Lock()

    # -- spilled-user fallback ----------------------------------------------
    def fallback_topk(self, topk: int) -> Tuple[np.ndarray, np.ndarray]:
        """Bias-only/popularity top-k for spilled (evicted) users.

        Scores are ``global_mean + item_bias`` for the bias variants (the
        personalization term of an absent row is unknowable) and zeros for
        funk — ``jax.lax.top_k`` ordering, so the item order is the same
        deterministic tie-break the personalized paths use.  Built once per
        (snapshot, topk) and cached: every spilled user gets the same row.
        """
        with self._build_lock:
            got = self._fallback_topk.get(topk)
            if got is None:
                scores = jnp.asarray(self.item_bias_vec, jnp.float32)
                if self.params.global_mean is not None:
                    scores = scores + jnp.float32(self.params.global_mean)
                s, i = jax.lax.top_k(scores, topk)
                got = (
                    np.asarray(s, np.float32),
                    np.asarray(i, np.int32),
                )
                self._fallback_topk[topk] = got
            return got

    # -- layouts -------------------------------------------------------------
    def stream_layout(self):
        with self._build_lock:
            return self._stream_layout_locked()

    def kernel_layout(self):
        with self._build_lock:
            if self._kernel_layout is None:
                self._kernel_layout = pad_catalog_for_topk_kernel(
                    self.params.q, self.r_i, self.item_bias_vec
                )
            return self._kernel_layout

    def shard_layout(self, n_model: int):
        """Streaming catalog tiles padded so the tile axis splits evenly over
        ``n_model`` shards; padding tiles carry -inf biases and can never
        win the merge.  One copy per shard count (NOT per topk)."""
        with self._build_lock:
            if n_model not in self._shard_layouts:
                q_tiles, b_tiles, offs = self._stream_layout_locked()
                pad_t = (-q_tiles.shape[0]) % n_model
                self._shard_layouts[n_model] = (
                    jnp.pad(q_tiles, ((0, pad_t), (0, 0), (0, 0))),
                    jnp.pad(b_tiles, ((0, pad_t), (0, 0)),
                            constant_values=_NEG_INF),
                    jnp.pad(offs, (0, pad_t)),
                )
            return self._shard_layouts[n_model]

    def _compact_k(self) -> int:
        """Latent columns the streaming layout must keep under compaction:
        every masked item row is zero beyond its effective rank, so columns
        past ``max(r_i)`` are zero for the *whole* catalog and can be
        truncated — this is what turns a tighter threshold into real CPU
        FLOP savings instead of multiply-by-zero work.  Rounded up to a
        multiple of 8 so threshold moves land on a handful of compiled
        shapes instead of retracing per distinct rank."""
        if not self.compact_latent or float(self.t_q) <= 0.0:
            return self.k
        r_max = max(int(jnp.max(self.r_i)), 1) if self.n_items else self.k
        return min(self.k, ((r_max + 7) // 8) * 8)

    def _stream_layout_locked(self):
        # shard_layout holds _build_lock already; inline the lazy build
        if self._stream_layout is None:
            qm = self.params.q.astype(jnp.float32) * rank_mask(self.r_i, self.k)
            k_eff = self._compact_k()
            if k_eff < self.k:
                qm = qm[:, :k_eff]
            self._stream_layout = tile_catalog(
                qm, self.item_bias_vec, self.block_n
            )
        return self._stream_layout

    def kernel_shard_layout(self, n_model: int):
        """Kernel-path catalog operands padded so each of ``n_model`` shards
        gets an equal, block-aligned item slab.  Padding rows carry rank 0
        and -inf bias, so the kernel's running top-k can never select them
        regardless of which shard they land on."""
        with self._build_lock:
            if n_model not in self._kernel_shard_layouts:
                q, r_i, bias = self.params.q, self.r_i, self.item_bias_vec
                n = q.shape[0]
                mult = TOPK_BLOCK_N * n_model
                pad_n = (-n) % mult
                pad_k = (-self.k) % TOPK_BLOCK_K
                qp = jnp.pad(q, ((0, pad_n), (0, pad_k)))
                rip = jnp.pad(r_i[:, None].astype(jnp.int32), ((0, pad_n), (0, 0)))
                biasp = jnp.pad(
                    bias.astype(jnp.float32)[:, None],
                    ((0, pad_n), (0, 0)),
                    constant_values=_NEG_INF,
                )
                self._kernel_shard_layouts[n_model] = (qp, rip, biasp)
            return self._kernel_shard_layouts[n_model]

    # -- incremental rebuilds (hot-swap fast path) ---------------------------
    def layouts_view(self):
        """Consistent copy of the built-layout set, taken under the build
        lock — the swap thread iterates it while the scheduler thread may
        still be lazily building layouts into this (previous) snapshot."""
        with self._build_lock:
            return (
                self._stream_layout,
                self._kernel_layout,
                dict(self._shard_layouts),
                dict(self._kernel_shard_layouts),
            )

    def clone_layouts_from(
        self, prev: "_Snapshot", touched_items: np.ndarray
    ) -> bool:
        """Carry ``prev``'s built layouts over to this snapshot, patching only
        the rows of ``touched_items`` — valid ONLY when thresholds, the
        catalog size, and the latent permutation are unchanged (the caller
        checks).  This is the double-buffer build of a hot swap: the
        rank/mask compute drops to O(touched * k), but note each ``.at[].set``
        runs outside jit and therefore copies its full buffer — per-swap
        memory traffic stays O(n * k), only the recompute is saved.

        Returns False — meaning "patch unsound, caller must full-rebuild" —
        when a latent-compacted layout is too narrow for a touched row's new
        effective rank (online updates grew a factor past the truncation
        width; the 8-column rounding slack in ``_compact_k`` makes this
        rare)."""
        k = self.k
        idx = jnp.asarray(touched_items, jnp.int32)
        q_rows = self.params.q[idx]
        r_rows = self.r_i[idx]
        qm_rows = q_rows.astype(jnp.float32) * rank_mask(r_rows, k)
        b_rows = self.item_bias_vec[idx]
        stream, kernel, shard, kernel_shard = prev.layouts_view()

        compact_widths = [
            layout[0].shape[2]
            for layout in (stream, *shard.values())
            if layout is not None and layout[0].shape[2] < k
        ]
        if compact_widths and int(jnp.max(r_rows)) > min(compact_widths):
            return False

        if stream is not None:
            q_tiles, b_tiles, offs = stream
            block_n = q_tiles.shape[1]
            kc = q_tiles.shape[2]
            t_idx, slot = idx // block_n, idx % block_n
            self._stream_layout = (
                q_tiles.at[t_idx, slot].set(qm_rows[:, :kc]),
                b_tiles.at[t_idx, slot].set(b_rows),
                offs,
            )
        if kernel is not None:
            qp, rip, biasp = kernel
            self._kernel_layout = (
                qp.at[idx, :k].set(q_rows.astype(qp.dtype)),
                rip.at[idx, 0].set(r_rows),
                biasp.at[idx, 0].set(b_rows),
            )
        for n_model, (q_tiles, b_tiles, offs) in shard.items():
            block_n = q_tiles.shape[1]
            kc = q_tiles.shape[2]
            t_idx, slot = idx // block_n, idx % block_n
            self._shard_layouts[n_model] = (
                q_tiles.at[t_idx, slot].set(qm_rows[:, :kc]),
                b_tiles.at[t_idx, slot].set(b_rows),
                offs,
            )
        for n_model, (qp, rip, biasp) in kernel_shard.items():
            self._kernel_shard_layouts[n_model] = (
                qp.at[idx, :k].set(q_rows.astype(qp.dtype)),
                rip.at[idx, 0].set(r_rows),
                biasp.at[idx, 0].set(b_rows),
            )
        return True

    def build_like(self, prev: "_Snapshot"):
        """Eagerly build every layout ``prev`` had built (full rebuild path —
        thresholds/geometry changed).  Keeps the first post-swap request from
        paying the build: the swap is double-buffered, not lazy."""
        stream, kernel, shard, kernel_shard = prev.layouts_view()
        if stream is not None:
            self.stream_layout()
        if kernel is not None:
            self.kernel_layout()
        for n_model in shard:
            self.shard_layout(n_model)
        for n_model in kernel_shard:
            self.kernel_shard_layout(n_model)

    def built_layouts(self):
        """Every device array currently materialized for this snapshot (used
        to block until the double-buffered build is actually resident)."""
        out = []
        for layout in (self._stream_layout, self._kernel_layout):
            if layout is not None:
                out.extend(layout)
        for table in (self._shard_layouts, self._kernel_shard_layouts):
            for layout in table.values():
                out.extend(layout)
        return out


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class ServingEngine:
    """Load a DP-MF model once; answer batched top-k requests forever.

    ``block_n`` sizes the item tiles of the *streaming* (``use_kernel=False``)
    layout only; the Pallas kernel path uses the MXU/VMEM-aligned block
    defaults of ``kernels.ops.pad_catalog_for_topk_kernel``.  ``max_batch``
    caps a scoring launch; larger requests are chunked.  All top-k entry
    points return ``(scores, indices)`` — the ``jax.lax.top_k`` ordering.

    The model state behind those entry points is a versioned snapshot;
    :meth:`swap` atomically publishes a new one (see the module docstring
    for the consistency contract).
    """

    def __init__(
        self,
        params: mf.MFParams,
        t_p=0.0,
        t_q=0.0,
        *,
        max_batch: int = 256,
        block_n: int = 1024,
        use_kernel: Optional[bool] = None,
        interpret: Optional[bool] = None,
        cache_size: int = 4096,
        user_history: Optional[np.ndarray] = None,
        allow_missing_history: bool = False,
        compact_latent: bool = False,
        user_remap: Optional[np.ndarray] = None,
        remap_epoch: int = 0,
    ):
        self.max_batch = max_batch
        self.block_n = block_n
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        self.use_kernel = use_kernel
        self.interpret = interpret
        self.cache_size = cache_size
        # ``compact_latent=True`` truncates the streaming layout's latent
        # axis to the catalog's max effective rank (rounded up to 8): with
        # pruning on, scoring FLOPs actually drop with the threshold — the
        # lever the SLO controller degrades along.  Scores can differ from
        # the full-width path by reduction-order ulps at t > 0 (exact at
        # t == 0, where no truncation happens), so it is opt-in.
        self.compact_latent = compact_latent

        history = self._resolve_history(
            params, user_history, allow_missing_history
        )
        cache = LRUCache(cache_size if params.implicit is not None else 0)
        self._snap = _Snapshot(
            0, params, t_p, t_q,
            block_n=block_n, cache=cache, user_history=history,
            compact_latent=compact_latent,
            user_remap=user_remap, remap_epoch=remap_epoch,
        )
        # Sharded scoring: compiled program per (mesh, topk, kernel-path) —
        # jit caches by function identity, so the shard_map closure must be
        # built once.  Layouts are passed as arguments, so compiled programs
        # survive swaps (recompiling only if the catalog geometry changes).
        self._sharded_fns = {}
        self._queue = None  # async frontend, created by start()/submit()
        self._queue_lock = threading.Lock()  # guards _queue transitions
        self._stopping = False               # stop() drain in progress
        self._swap_lock = threading.Lock()   # serializes swap() builders

    @staticmethod
    def _resolve_history(params, user_history, allow_missing_history):
        history = None if user_history is None else np.asarray(user_history)
        if params.implicit is not None and history is None:
            if not allow_missing_history:
                raise ValueError(
                    "SVD++ params need user_history (see "
                    "data.build_user_history), or pass "
                    "allow_missing_history=True to serve from p alone"
                )
            # Empty histories: every entry is the implicit table's padding
            # row, so user vectors reduce to p_u exactly.
            history = np.full(
                (params.p.shape[0], 1), params.q.shape[0], np.int32
            )
        return history

    # -- construction -------------------------------------------------------
    @classmethod
    def from_checkpoint(
        cls, directory: str, *, step: Optional[int] = None, **kwargs
    ) -> "ServingEngine":
        """Build an engine from a trainer checkpoint directory: restores the
        full ``MFParams`` plus the trained thresholds
        (:func:`load_mf_checkpoint`); ``kwargs`` pass to the constructor."""
        params, t_p, t_q, _, _ = load_mf_checkpoint(directory, step=step)
        return cls(params, t_p, t_q, **kwargs)

    # -- versioned state accessors ------------------------------------------
    @property
    def version(self) -> int:
        """Monotonic version of the currently served snapshot (0 at load;
        each :meth:`swap` increments it)."""
        return self._snap.version

    @property
    def params(self) -> mf.MFParams:
        """Factor tables of the current snapshot."""
        return self._snap.params

    @property
    def t_p(self):
        """User-side pruning threshold of the current snapshot."""
        return self._snap.t_p

    @property
    def t_q(self):
        """Item-side pruning threshold of the current snapshot."""
        return self._snap.t_q

    @property
    def r_i(self):
        """(n,) per-item effective ranks of the current snapshot."""
        return self._snap.r_i

    @property
    def num_users(self) -> int:
        """User-table rows of the current snapshot (valid request ids are
        ``[0, num_users)``)."""
        return self._snap.num_users

    @property
    def num_external(self) -> int:
        """Size of the valid *request* id domain: equals :attr:`num_users`
        without an eviction remap, else the external-id domain (grow-only
        even while compactions shrink the physical table)."""
        return self._snap.num_external

    @property
    def remap_epoch(self) -> int:
        """Compaction counter of the current snapshot's id remap (0 when
        eviction was never armed upstream)."""
        return self._snap.remap_epoch

    @property
    def n_items(self) -> int:
        """Catalog size of the current snapshot."""
        return self._snap.n_items

    @property
    def k(self) -> int:
        """Latent dimension."""
        return self._snap.k

    @property
    def user_history(self) -> Optional[np.ndarray]:
        """(m, H) SVD++ implicit-history matrix, or None for non-SVD++."""
        return self._snap.user_history

    @property
    def vector_cache(self) -> LRUCache:
        """Hot-user vector LRU of the current snapshot (SVD++ only holds
        entries; other variants use a zero-capacity cache)."""
        return self._snap.cache

    # -- hot swap ------------------------------------------------------------
    def swap(
        self,
        params: mf.MFParams,
        t_p=None,
        t_q=None,
        *,
        touched_users: Optional[Iterable[int]] = None,
        touched_items: Optional[Iterable[int]] = None,
        touched_implicit_items: Optional[Iterable[int]] = None,
        user_history: Optional[np.ndarray] = None,
        user_remap: Optional[np.ndarray] = None,
        remap_epoch: Optional[int] = None,
    ) -> int:
        """Atomically publish a new factor version; returns its number.

        Zero-downtime contract: requests never observe a half-swapped model.
        A scoring batch in flight when the swap lands completes on the old
        snapshot (per-version determinism); batches popped afterwards score
        on the new one.  The new snapshot's layouts are built double-buffered
        *before* the flip:

        * ``touched_items`` given, thresholds/catalog-geometry unchanged —
          the previous layouts are patched at only those rows: O(touched * k)
          compute (rank/mask work), though each patched buffer is still
          copied whole (XLA scatter outside jit), so memory traffic per swap
          remains O(n * k);
        * otherwise (recalibrated thresholds, a latent-axis rearrange, or a
          grown catalog) — full rebuild of whatever layouts were in use.

        The hot-user LRU survives the swap minus the stale entries: the
        ``touched_users`` plus, for SVD++, every user whose history contains
        a row of ``touched_implicit_items``/``touched_items`` (their cached
        aggregation folds those implicit rows in).  Pass
        ``touched_users=None`` to drop the whole cache.

        Tables may grow (cold-start users/items appended by the online
        updater); they may not shrink — queued request ids stay valid.
        The one exception is an eviction compaction: a ``remap_epoch``
        *bump* (with its ``user_remap`` table) may shrink the user table —
        external request ids stay valid through the remap, in-flight
        batches finish on the previous snapshot, and the swap is forced
        down the full-rebuild path with a fresh vector cache (physical
        indices moved).  Omitting both remap kwargs carries the previous
        snapshot's remap forward unchanged.
        """
        # normalize one-shot iterables up front: the touched sets are walked
        # several times below (layout patch, user-const patch, LRU pruning)
        if touched_users is not None:
            touched_users = np.asarray(list(touched_users), np.int64)
        if touched_items is not None:
            touched_items = np.asarray(list(touched_items), np.int64)
        if touched_implicit_items is not None:
            touched_implicit_items = np.asarray(
                list(touched_implicit_items), np.int64
            )
        with self._swap_lock:
            prev = self._snap
            if remap_epoch is None:
                remap_epoch = prev.remap_epoch
                if user_remap is None:
                    user_remap = prev.user_remap
            remap_changed = int(remap_epoch) != prev.remap_epoch
            if remap_changed:
                # compaction barrier: physical rows were renumbered, so no
                # previous layout, cached vector, or touched-row delta can
                # be patched — full rebuild, whole-cache drop
                if user_remap is None:
                    raise ValueError(
                        "a remap_epoch bump must carry its user_remap table"
                    )
                touched_users = None
                touched_items = None
                touched_implicit_items = None
            if not remap_changed and (
                params.p.shape[0] < prev.num_users
                or params.q.shape[0] < prev.n_items
            ):
                raise ValueError(
                    "swap cannot shrink the user/item tables "
                    f"({prev.num_users}x{prev.n_items} -> "
                    f"{params.p.shape[0]}x{params.q.shape[0]}): queued "
                    "requests may already reference the trailing rows "
                    "(only an eviction compaction — a remap_epoch bump — "
                    "may shrink the user table)"
                )
            t_p = prev.t_p if t_p is None else t_p
            t_q = prev.t_q if t_q is None else t_q

            if user_history is None and prev.user_history is not None:
                user_history = self._grow_history(
                    prev.user_history, params, prev.n_items
                )
            elif params.implicit is not None and user_history is None:
                user_history = self._resolve_history(params, None, True)

            same_geometry = (
                params.q.shape[0] == prev.n_items
                and params.p.shape[1] == prev.k
                and float(jnp.asarray(t_q, jnp.float32)) == float(prev.t_q)
            )
            incremental = touched_items is not None and same_geometry
            idx = None
            r_i_pre = None
            user_const_pre = None
            if incremental:
                idx = np.unique(np.asarray(list(touched_items), np.int64))
                if idx.size:
                    # pad to the next power of two (duplicating the last
                    # index — a duplicate .set writes the same row value) so
                    # the scatter programs retrace O(log n) times, not once
                    # per distinct touched count
                    bucket = 1 << (int(idx.size) - 1).bit_length()
                    idx = np.pad(idx, (0, bucket - idx.size), mode="edge")
                    jidx = jnp.asarray(idx, jnp.int32)
                    # item ranks: reduce only the touched rows, patch the rest
                    r_i_pre = prev.r_i.at[jidx].set(
                        effective_ranks(
                            params.q[jidx], jnp.asarray(t_q, jnp.float32)
                        )
                    )
                else:
                    r_i_pre = prev.r_i
                user_const_pre = self._patch_user_const(
                    prev, params, touched_users
                )

            new = _Snapshot(
                prev.version + 1, params, t_p, t_q,
                block_n=self.block_n,
                cache=self._carry_cache(
                    prev, params, touched_users, touched_items,
                    touched_implicit_items, user_history,
                ),
                user_history=user_history,
                r_i=r_i_pre,
                user_const=user_const_pre,
                compact_latent=self.compact_latent,
                user_remap=user_remap,
                remap_epoch=int(remap_epoch),
            )

            if incremental:
                if idx is not None and idx.size:
                    if not new.clone_layouts_from(prev, idx):
                        # a touched row's rank outgrew the compacted latent
                        # width: the patch would truncate real factors —
                        # rebuild the layouts at the new width instead
                        new._stream_layout = None
                        new._kernel_layout = None
                        new._shard_layouts = {}
                        new._kernel_shard_layouts = {}
                        new.build_like(prev)
                else:  # nothing touched on the item side: layouts carry over
                    (new._stream_layout, new._kernel_layout,
                     new._shard_layouts,
                     new._kernel_shard_layouts) = prev.layouts_view()
            else:
                new.build_like(prev)
            # the flip must publish a *resident* double buffer, not a pile of
            # pending device computations the first request would wait on
            built = new.built_layouts()
            if built:
                jax.block_until_ready(built)

            self._snap = new  # atomic: in-flight batches hold `prev`
            return new.version

    @staticmethod
    def _patch_user_const(prev, params, touched_users) -> Optional[np.ndarray]:
        """Incremental-swap user constants: copy the previous (m,) vector and
        rewrite only the touched (and newly grown) rows.  Returns None —
        meaning "recompute from scratch" — whenever the patch could be wrong:
        no bias term, no touched-user list, or a moved global mean."""
        if params.user_bias is None:
            return None
        if prev.user_const is None or touched_users is None:
            return None
        if (
            prev.params.global_mean is None
            or float(params.global_mean) != float(prev.params.global_mean)
        ):
            return None
        m_new = params.p.shape[0]
        tu = np.asarray(list(touched_users), np.int64)
        if m_new > prev.num_users:
            # grown rows are rewritten unconditionally — correctness must not
            # depend on the caller having listed them as touched
            tu = np.concatenate(
                [tu, np.arange(prev.num_users, m_new, dtype=np.int64)]
            )
        uc = np.empty((m_new,), np.float32)
        uc[: prev.num_users] = prev.user_const
        if tu.size:
            uc[tu] = np.asarray(
                params.user_bias[jnp.asarray(tu), 0].astype(jnp.float32)
                + params.global_mean
            )
        return uc

    @staticmethod
    def _grow_history(history, params, old_n_items):
        """Pad the history matrix for grown user tables and remap the padding
        sentinel (== old catalog size) when the item table grew under it."""
        new_m = params.p.shape[0]
        new_n = params.q.shape[0]
        out = history
        if new_n != old_n_items and params.implicit is not None:
            out = out.copy()
            out[out == old_n_items] = new_n
        if new_m > history.shape[0]:
            pad_rows = np.full(
                (new_m - history.shape[0], history.shape[1]),
                new_n if params.implicit is not None else old_n_items,
                history.dtype,
            )
            out = np.concatenate([out, pad_rows], axis=0)
        return out

    def _carry_cache(
        self, prev, params, touched_users, touched_items,
        touched_implicit_items, user_history,
    ) -> LRUCache:
        """Hot-user LRU for the next snapshot: previous entries minus the
        stale ones (touched-rows-only invalidation)."""
        capacity = self.cache_size if params.implicit is not None else 0
        if capacity != prev.cache.capacity or touched_users is None:
            return LRUCache(capacity)
        stale = set(int(u) for u in touched_users)
        if params.implicit is not None:
            # an SVD++ user vector folds in the implicit rows of its history:
            # users whose history intersects the touched implicit rows are
            # stale even though their own p row never moved.  Only users
            # actually IN the cache can hold a stale entry, so the scan is
            # O(|cache| * hist) — not O(num_users * hist) — per swap.
            items = set(
                int(i) for i in
                (touched_items if touched_items is not None else ())
            ) | set(
                int(i) for i in
                (touched_implicit_items
                 if touched_implicit_items is not None else ())
            )
            cached = [u for u in prev.cache.keys() if u not in stale]
            if items and cached and user_history is not None:
                hit = np.isin(
                    user_history[np.asarray(cached, np.int64)],
                    np.fromiter(items, np.int64, len(items)),
                ).any(axis=1)
                stale |= set(
                    int(u) for u, h in zip(cached, hit) if h
                )
        return prev.cache.copy_without(stale)

    # -- user vectors --------------------------------------------------------
    def _user_vectors(self, snap: _Snapshot, user_ids: np.ndarray) -> jnp.ndarray:
        """(B, k) user vectors: plain rows, or SVD++ history-aggregated rows
        memoized per user in the LRU (the hot-user cache)."""
        if snap.params.implicit is None:
            return snap.params.p[jnp.asarray(user_ids)]
        rows = [snap.cache.get(int(u)) for u in user_ids]
        missing = [i for i, r in enumerate(rows) if r is None]
        if missing:
            miss_ids = np.asarray([user_ids[i] for i in missing], np.int32)
            hist = jnp.asarray(snap.user_history[miss_ids])
            fresh = np.asarray(
                mf._user_vector(snap.params, jnp.asarray(miss_ids), hist)
            )
            for slot, row in zip(missing, fresh):
                rows[slot] = row
                snap.cache.put(int(user_ids[slot]), row)
        return jnp.asarray(np.stack(rows))

    # -- scoring -------------------------------------------------------------
    def _masked_user_block(self, snap: _Snapshot, pu: jnp.ndarray) -> jnp.ndarray:
        r_u = effective_ranks(pu, snap.t_p)
        return pu.astype(jnp.float32) * rank_mask(r_u, snap.k)

    def _topk_block(self, snap: _Snapshot, pu: jnp.ndarray, topk: int):
        if self.use_kernel:
            return self._topk_block_kernel(snap, pu, topk)
        q_tiles, b_tiles, offs = snap.stream_layout()
        pm = self._masked_user_block(snap, pu)
        if q_tiles.shape[2] < pm.shape[1]:
            # latent-compacted layout: user columns past the catalog's max
            # effective rank only ever multiply zeros — drop them too
            pm = pm[:, : q_tiles.shape[2]]
        return stream_topk_tiles(pm, q_tiles, b_tiles, offs, topk=topk)

    def _topk_block_kernel(self, snap: _Snapshot, pu: jnp.ndarray, topk: int):
        qp, rip, biasp = snap.kernel_layout()
        r_u = effective_ranks(pu, snap.t_p)
        pp, rup = pad_users_for_topk_kernel(pu, r_u)
        scores, idx = pruned_topk_padded(
            pp, qp, rup, rip, biasp,
            topk=topk, n_items=snap.n_items,
            interpret=self._interpret(),
        )
        return scores[: pu.shape[0], :topk], idx[: pu.shape[0], :topk]

    def _interpret(self) -> bool:
        return (
            jax.default_backend() != "tpu"
            if self.interpret is None
            else self.interpret
        )

    def _validate_request(self, user_ids, topk: int) -> np.ndarray:
        return self._validate_for(self._snap, user_ids, topk)

    @staticmethod
    def _validate_for(snap: _Snapshot, user_ids, topk: int) -> np.ndarray:
        if not 0 < topk <= snap.n_items:
            raise ValueError(
                f"topk must be in [1, {snap.n_items}], got {topk}"
            )
        ids = np.asarray(user_ids, np.int32).reshape(-1)
        # jnp gathers clamp out-of-range indices silently — that would serve
        # the *last* user's recommendations to an unknown user id.  With an
        # eviction remap the request domain is the *external* ids (which
        # only ever grows), not the physical table.
        bad = (ids < 0) | (ids >= snap.num_external)
        if bad.any():
            raise ValueError(
                f"unknown user ids {ids[bad][:5].tolist()} "
                f"(catalog has {snap.num_external} users)"
            )
        return ids

    @staticmethod
    def _translate_ids(
        snap: _Snapshot, ids: np.ndarray
    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """External ids → physical rows under the snapshot's remap.

        Returns ``(physical_ids, evicted_mask-or-None)``; evicted users
        point at placeholder row 0 (scored then discarded — their result
        rows are overwritten by :meth:`_Snapshot.fallback_topk`)."""
        if snap.user_remap is None:
            return ids, None
        phys = snap.user_remap[ids].astype(np.int64)
        evicted = phys < 0
        if not evicted.any():
            return phys.astype(np.int32), None
        return np.where(evicted, 0, phys).astype(np.int32), evicted

    @staticmethod
    def _apply_fallback(
        snap: _Snapshot,
        evicted: Optional[np.ndarray],
        topk: int,
        out_s: np.ndarray,
        out_i: np.ndarray,
    ) -> Tuple[np.ndarray, np.ndarray]:
        if evicted is not None:
            fs, fi = snap.fallback_topk(topk)
            out_s[evicted] = fs
            out_i[evicted] = fi
        return out_s, out_i

    def _run_chunked(self, snap: _Snapshot, ids: np.ndarray, topk: int, block_fn):
        """Shared request loop: split into max_batch chunks, pad each chunk
        to its power-of-two bucket (bounds the jit cache to log2(max_batch)
        shapes per scoring program), score, fold user constants back in."""
        out_s = np.empty((len(ids), topk), np.float32)
        out_i = np.empty((len(ids), topk), np.int32)
        for lo in range(0, len(ids), self.max_batch):
            chunk = ids[lo : lo + self.max_batch]
            with tracing.span("repro.serving.gather"):
                bucket = bucket_size(len(chunk), self.max_batch)
                padded = np.pad(chunk, (0, bucket - len(chunk)), mode="edge")
                pu = self._user_vectors(snap, padded)
            with tracing.span(
                "repro.serving.launch", users=len(chunk), bucket=bucket
            ):
                scores, idx = block_fn(pu, topk)
            with tracing.span("repro.serving.fetch"):
                scores = np.asarray(scores[: len(chunk)])
                idx = np.asarray(idx[: len(chunk)])
                if snap.user_const is not None:
                    scores = scores + snap.user_const[chunk][:, None]
                out_s[lo : lo + len(chunk)] = scores
                out_i[lo : lo + len(chunk)] = idx
        return out_s, out_i

    def topk(
        self, user_ids, topk: int = 10
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k items for a batch of users.  Returns ``(scores, indices)``
        as (B, topk) numpy arrays — the ``jax.lax.top_k`` ordering, same as
        ``kernels.ops.pruned_topk`` and ``ref.pruned_topk_ref`` — identical
        to dense score-and-argsort."""
        with tracing.span("repro.serving.topk", users=np.size(user_ids)):
            snap = self._snap  # captured once: the whole batch serves one version
            ids = self._validate_for(snap, user_ids, topk)
            phys, evicted = self._translate_ids(snap, ids)
            out_s, out_i = self._run_chunked(
                snap, phys, topk,
                lambda pu, k_: self._topk_block(snap, pu, k_),
            )
            return self._apply_fallback(snap, evicted, topk, out_s, out_i)

    # -- sharded catalog -----------------------------------------------------
    def _sharded_program(self, mesh, topk: int, kernel: bool):
        """Compiled shard_map scoring program for (mesh, topk, path).  Built
        once: jit caches by function identity, so rebuilding the closure per
        request would retrace and recompile every call.  Layouts enter as
        arguments, so the program survives hot swaps."""
        from repro.distributed.sharding import (
            serving_topk_kernel_specs,
            serving_topk_specs,
        )

        key = (mesh, topk, kernel)
        if key not in self._sharded_fns:
            if kernel:
                in_specs, out_specs = serving_topk_kernel_specs(mesh)
                interpret = self._interpret()

                def body(pu_blk, t_p, qp, rip, biasp):
                    n_loc = qp.shape[0]
                    r_u = effective_ranks(pu_blk, t_p)
                    pp, rup = pad_users_for_topk_kernel(pu_blk, r_u)
                    # padding rows inside the slab carry -inf bias, so every
                    # slab can claim its full extent as valid items
                    s, i = pruned_topk_padded(
                        pp, qp, rup, rip, biasp,
                        topk=topk, n_items=n_loc, interpret=interpret,
                    )
                    b = pu_blk.shape[0]
                    local_s = s[:b, :topk]
                    local_i = (
                        i[:b, :topk] + jax.lax.axis_index("model") * n_loc
                    )
                    return _merge_over_model(local_s, local_i, b, topk)
            else:
                in_specs, out_specs = serving_topk_specs(mesh)

                def body(pm_blk, qt, bt, off):
                    local_s, local_i = stream_topk_tiles(
                        pm_blk, qt, bt, off, topk=topk
                    )
                    return _merge_over_model(
                        local_s, local_i, pm_blk.shape[0], topk
                    )

            self._sharded_fns[key] = jax.jit(jax.shard_map(
                body,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,
            ))
        return self._sharded_fns[key]

    def topk_sharded(
        self, user_ids, topk: int = 10, *, mesh=None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Mesh-sharded top-k, 2-D when the mesh has both axes.

        Item tiles shard over the mesh's "model" axis (PR 1); user rows —
        and with them the per-request user-factor fan-out — shard over the
        data axes when present (``distributed.sharding.serving_topk_specs``),
        so a (2, 4) ``("data", "model")`` mesh scores each user slab against
        each catalog slice on its own device.  Per shard: streaming top-k
        (or the Pallas kernel when ``use_kernel=True`` — each shard runs the
        fused pruned-score+top-k kernel on its own item slab), one all-gather
        of the (b, topk) shard winners over "model", local merge —
        collective traffic is O(b * topk), independent of catalog size, and
        the batch axis never leaves its data shard.  Returns ``(scores,
        indices)`` like :meth:`topk`; requests go through the same
        chunk/bucket loop, so batch shapes (and thus compiled programs) stay
        bounded."""
        from repro.distributed.sharding import serving_row_multiple

        snap = self._snap
        ids = self._validate_for(snap, user_ids, topk)
        ids, evicted = self._translate_ids(snap, ids)
        mesh = mesh if mesh is not None else jax.sharding.get_abstract_mesh()
        if "model" not in mesh.axis_names:
            raise ValueError("topk_sharded needs a mesh with a 'model' axis")
        n_model = mesh.shape["model"]
        kernel = self.use_kernel
        layout = (
            snap.kernel_shard_layout(n_model) if kernel
            else snap.shard_layout(n_model)
        )
        fn = self._sharded_program(mesh, topk, kernel)
        row_mult = serving_row_multiple(mesh)

        def block_fn(pu, k_):
            b = pu.shape[0]
            pad = (-b) % row_mult  # equal user slabs per data shard
            if kernel:
                pm = pu.astype(jnp.float32)
            else:
                pm = self._masked_user_block(snap, pu)
                if layout[0].shape[2] < pm.shape[1]:
                    pm = pm[:, : layout[0].shape[2]]
            if pad:
                pm = jnp.pad(pm, ((0, pad), (0, 0)))
            if kernel:
                scores, idx = fn(pm, snap.t_p, *layout)
            else:
                scores, idx = fn(pm, *layout)
            return scores[:b], idx[:b]

        out_s, out_i = self._run_chunked(snap, ids, topk, block_fn)
        return self._apply_fallback(snap, evicted, topk, out_s, out_i)

    # -- async frontend ------------------------------------------------------
    def start(self, *, mesh=None, **queue_kwargs):
        """Start the async request pipeline; returns the
        :class:`~repro.serving.queue.RequestQueue`.

        With ``mesh`` the queue scores through :meth:`topk_sharded` on that
        mesh (1-D or 2-D); otherwise through the local :meth:`topk` path.
        Queue kwargs (``max_batch``, ``max_pending``, ``linger_ms``) pass
        through.  The queue's single scheduler thread is the only thread
        that touches the scoring paths, so no engine locking is needed.

        Restartable: after :meth:`stop` (or after the attached queue was
        closed directly) ``start`` brings up a fresh queue — the lifecycle
        the online publisher's swap-time drains rely on.
        """
        with self._queue_lock:
            return self._start_locked(mesh=mesh, **queue_kwargs)

    def _start_locked(self, *, mesh=None, **queue_kwargs):
        from repro.serving.queue import RequestQueue

        if self._queue is not None:
            if not self._queue.closed:
                raise RuntimeError("engine already has a running request queue")
            self._queue = None  # stale handle: queue was closed directly
        score_fn = None
        if mesh is not None:
            score_fn = lambda users, k: self.topk_sharded(users, k, mesh=mesh)
        self._queue = RequestQueue(self, score_fn=score_fn, **queue_kwargs)
        return self._queue

    def submit(
        self, user_id: int, topk: int = 10, *, timeout=None, priority: int = 0
    ):
        """Async single-user request: returns a ``concurrent.futures.Future``
        resolving to ``(scores, item_ids)`` — (topk,) rows, byte-identical
        to the caller's row of :meth:`topk`.  Poll with ``future.done()``,
        block with ``future.result(timeout)``.  ``priority`` orders requests
        inside a deadline bucket (lower = sooner; see ``serving/queue.py``).
        Starts a default queue on first use; call :meth:`start` first to
        configure it.  Safe from any thread (first-submit races resolve to
        one shared queue).  While :meth:`stop` is draining, new submits are
        rejected with ``RuntimeError`` — they must NOT resurrect a fresh
        queue mid-shutdown (the pre-fix behaviour: a zombie queue nobody
        owned, whose futures stranded forever at process exit)."""
        with self._queue_lock:
            if self._stopping:
                raise RuntimeError("engine is stopping; request rejected")
            if self._queue is None or self._queue.closed:
                self._start_locked()
            queue = self._queue
        return queue.submit(user_id, topk, timeout=timeout, priority=priority)

    @property
    def queue_depth(self) -> int:
        """Requests currently queued or being scored by the async frontend
        (0 when no queue is attached) — the fleet router's load signal."""
        with self._queue_lock:
            queue = self._queue
        return 0 if queue is None or queue.closed else queue.depth

    def stop(self) -> None:
        """Drain and stop the async pipeline: every request already accepted
        completes (scored, expired, or failed — never stranded) before this
        returns.  Concurrent :meth:`submit` calls during the drain are
        rejected instead of auto-starting a new queue.  Idempotent: a second
        stop (or stop before any start) is a no-op; :meth:`start` /
        :meth:`submit` work again afterwards."""
        with self._queue_lock:
            if self._stopping:
                return  # another thread's stop() owns the drain
            queue, self._queue = self._queue, None
            self._stopping = True
        try:
            if queue is not None:
                queue.close()  # outside the lock: close() joins the scheduler
        finally:
            with self._queue_lock:
                self._stopping = False

    # -- convenience ---------------------------------------------------------
    def recommend(self, user_ids, topk: int = 10):
        """JSON-friendly form: list of per-user [{item, score}, ...]."""
        scores, idx = self.topk(user_ids, topk)
        return [
            [
                {"item": int(i), "score": round(float(s), 4)}
                for i, s in zip(row_i, row_s)
            ]
            for row_i, row_s in zip(idx, scores)
        ]


def _merge_over_model(local_s, local_i, b: int, topk: int):
    """Cross-shard merge of per-shard (b, topk) winners: one all-gather over
    "model", then a local top-k over the n_model * topk candidates."""
    gs = jax.lax.all_gather(local_s, "model")  # (n_model, b, topk)
    gi = jax.lax.all_gather(local_i, "model")
    cand_s = jnp.moveaxis(gs, 0, 1).reshape(b, -1)
    cand_i = jnp.moveaxis(gi, 0, 1).reshape(b, -1)
    merged_s, sel = jax.lax.top_k(cand_s, topk)
    return merged_s, jnp.take_along_axis(cand_i, sel, axis=1)
