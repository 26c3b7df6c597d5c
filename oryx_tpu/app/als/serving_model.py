"""ALS serving model: in-memory factors + batched on-device top-N.

Rebuild of ALSServingModel (app/oryx-app-serving/.../als/model/
ALSServingModel.java:58-496) and its manager (ALSServingModelManager.java:
46-176), redesigned TPU-first: where the reference shards the item matrix
into LSH partitions scanned by a thread pool (LocalitySensitiveHash.java,
TopNConsumer.java), this model keeps a packed device copy of Y and
computes top-N as ONE batched matvec + lax.top_k on the accelerator — an
exact scan that is faster than the reference's approximate LSH probe at
millions of items (SURVEY.md §2.12 'Request parallelism'). The packed
copy refreshes lazily when vectors change (the survey's 'periodic
re-upload of dirty shards' strategy for incremental state vs immutable
device arrays).

State mirrored from the reference: X and Y FeatureVectors, per-user
known-item sets, expected-ID sets driving get_fraction_loaded
(ALSServingModel.java:461-475), a cached YtY solver invalidated on Y
writes (:357-373), and retain-recent rotation (:382-441). YtY itself
comes from the packed device copy wherever that copy holds the item rows
as they are (one pass of ``ops/gram.py`` over it; float64 sums of
per-block partials), and from the host store's double-precision loop
(``get_vtv``) for a model whose device copy is quantized, an IVF index, or
absent.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Iterable, Iterator

import numpy as np

from oryx_tpu.api.serving import AbstractServingModelManager, ServingModel
from oryx_tpu.app import pmml as app_pmml
from oryx_tpu.app.als.common import apply_up_lines, consume_blocks_columnar
from oryx_tpu.bus.core import KeyMessage
from oryx_tpu.common.config import Config
from oryx_tpu.common import metrics, profiling, tracing
from oryx_tpu.common.lang import ReadWriteLock
from oryx_tpu.common.text import read_json
from oryx_tpu.common.vectormath import Solver, get_solver
from oryx_tpu.native.store import make_feature_vectors
from oryx_tpu.ops import gram as gram_ops
from oryx_tpu.ops import ivf as ivf_ops
from oryx_tpu.ops import topn as topn_ops
from oryx_tpu.serving.batcher import score_default, score_indexed_default

log = logging.getLogger(__name__)

# What the staged user matrix leaves free on a device: the scan's own
# buffers (two passes in flight, each its gathered queries, its [b, k]
# results and the program's temporaries: tens of MB at 128 rows) and the
# allocator's fragmentation. Not in it, because a read-only replica never
# pays them: the copy that a row update of the item matrix or of the staged
# user matrix makes (both scatters donate nothing, a pass in flight may
# hold the old array; docs/serving-scan.md).
USER_STAGE_RESERVE_BYTES = 1 << 30
# A backend that reports no memory statistics (the CPU of tests and
# development, where the "device" is the host's own memory and a staged
# copy doubles what the store holds) keeps the bound this rule replaced.
USER_STAGE_DEFAULT_BUDGET_BYTES = 2 << 30


def user_stage_budget(devices) -> int:
    """Bytes the staged user matrix may take on each of ``devices`` (it is
    held whole on every one): the smallest, over them, of the allocator's
    limit less what is in use, less ``USER_STAGE_RESERVE_BYTES``; never
    negative. Read when a (re)stage is about to allocate;
    ``_rebuild_x_staging`` sees to it that the item matrix is counted."""
    free = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        if "bytes_limit" not in stats or "bytes_in_use" not in stats:
            return USER_STAGE_DEFAULT_BUDGET_BYTES
        free.append(int(stats["bytes_limit"]) - int(stats["bytes_in_use"]))
    if not free:
        return USER_STAGE_DEFAULT_BUDGET_BYTES
    return max(0, min(free) - USER_STAGE_RESERVE_BYTES)


class _UserVanished(Exception):
    """A user listed by the store had no vector a moment later."""


class ALSServingModel(ServingModel):
    def __init__(
        self,
        features: int,
        implicit: bool,
        refresh_sec: float = 0.2,
        sample_rate: float = 1.0,
        score_dtype: str = "float32",
        shard_items: bool = False,
        device_user_matrix: bool = True,
    ) -> None:
        self.features = features
        self.implicit = implicit
        # stage X on device next to Y so /recommend for a known user ships
        # an int32 row index instead of a query vector (index submit);
        # only meaningful for the exact-device-scan path
        self.device_user_matrix = device_user_matrix
        self._x_staging = bool(device_user_matrix) and sample_rate >= 1.0
        self._x_stage_refused = False  # the budget refused it: _refuse_x_staging
        # row-shard Y over all local devices (the kernel on every shard,
        # candidates merged across chips; X staged on each): the serving
        # mode of a catalog past one chip's memory, docs/serving-scan.md
        self.shard_items = shard_items
        # item-matrix dtype for device scoring: bfloat16 halves HBM traffic
        # (the serving bottleneck at millions of items) at ~1e-2 relative
        # score precision — near-tie ranks may swap, like LSH's trade-off.
        # int8 halves the SCANNED bytes again (row-quantized primary plane,
        # total memory ~bf16 counting the residual plane) and rescoring the
        # oversampled candidates against the residual keeps top-10 recall
        # >= 0.99 of float32 — see docs/serving-scan.md
        self.score_dtype = score_dtype
        # LSH candidate pruning is opt-in (sample-rate < 1): the exact
        # device matvec is the TPU fast path, LSH the CPU-parity fallback
        # (ALSServingModel.java:58-124 partitions Y this way always)
        self.lsh = None
        if sample_rate < 1.0:
            import os

            from oryx_tpu.app.als.lsh import LocalitySensitiveHash

            self.lsh = LocalitySensitiveHash(sample_rate, features, os.cpu_count() or 1)
        self.x = make_feature_vectors()
        self.y = make_feature_vectors()
        self._known_lock = ReadWriteLock()
        self._known_items: dict[str, set[str]] = {}
        self._expected_lock = threading.Lock()
        self._expected_users: set[str] = set()
        self._expected_items: set[str] = set()
        self._solver_lock = threading.Lock()
        self._yty_solver: Solver | None = None
        # packed device copy of Y
        self._cache_lock = threading.Lock()
        self._y_dirty = True
        # never built: the first build is due whatever time.monotonic()
        # reads (0.0 made a machine up for less than refresh_sec wait)
        self._y_built_at = float("-inf")
        self._refresh_sec = refresh_sec
        self._y_ids: list[str] = []
        self._y_index: dict[str, int] = {}
        self._y_matrix = None  # device array [n, k]
        self._y_host: np.ndarray | None = None  # host copy, LSH path only
        self._y_partitions: np.ndarray | None = None  # LSH partition per row
        # incremental refresh state: ids written since the last build, and
        # whether membership may have shrunk (rotation) forcing a rebuild
        self._dirty_ids: set[str] = set()
        self._y_full_rebuild = True
        # ANN maintenance handshake (serving/maintain.py): the build epoch
        # bumps on every full rebuild/index swap so a compaction whose
        # snapshot predates the current id space is discarded at install;
        # the pressure callback wakes the maintainer when a fold-in batch
        # crosses the overlay watermark or spills
        self._y_build_epoch = 0
        self._y_snapshot_epoch = -1
        # bumps on every rotation (retain_recent_and_item_ids): an index
        # adoption built from a pre-rotation store snapshot is discarded
        self._y_rotation_epoch = 0
        self._index_pressure_cb = None
        self._index_generation: str | None = None
        # device copy of X (query matrix for index-submitted /recommend)
        self._x_ids: list[str] = []
        self._x_index: dict[str, int] = {}
        self._x_matrix = None  # device [n, k] float32
        self._x_dirty_ids: set[str] = set()
        self._x_dirty = True
        self._x_full_rebuild = True
        self._x_built_at = float("-inf")  # never built, as _y_built_at
        self._x_capacity = 0
        self._x_building = False
        self._x_restage_thread: threading.Thread | None = None
        self._x_epoch = 0  # bumped by rotation: invalidates in-flight restages
        # taken here so that the counter is in every snapshot from the
        # start: absent means the program does not count this, never 0
        self._unstaged_requests = metrics.registry.counter("serving.users.unstaged-requests")
        self._m_foldin_seconds = metrics.registry.histogram("serving.foldin.seconds")
        self._m_foldin_requests = metrics.registry.counter("serving.foldin.requests")
        self._m_foldin_items = metrics.registry.counter("serving.foldin.items")
        self._m_yty_seconds = metrics.registry.histogram("serving.yty.build.seconds")
        self._m_yty_builds = {
            "device": metrics.registry.counter("serving.yty.builds.device"),
            "host": metrics.registry.counter("serving.yty.builds.host"),
        }

    # -- vectors -------------------------------------------------------------

    def get_user_vector(self, user: str) -> np.ndarray | None:
        return self.x.get_vector(user)

    def get_item_vector(self, item: str) -> np.ndarray | None:
        return self.y.get_vector(item)

    def set_user_vector(self, user: str, vector: np.ndarray) -> None:
        self.x.set_vector(user, vector)
        with self._expected_lock:
            self._expected_users.discard(user)
        if self._x_staging:
            with self._cache_lock:
                self._x_dirty = True
                self._x_dirty_ids.add(user)

    def set_item_vector(self, item: str, vector: np.ndarray) -> None:
        self.y.set_vector(item, vector)
        with self._expected_lock:
            self._expected_items.discard(item)
        with self._cache_lock:
            self._y_dirty = True
            self._dirty_ids.add(item)
        self._drop_yty_solver()

    def set_user_vectors(self, users: list[str], vectors: np.ndarray) -> None:
        """Batched set: one native store call + one lock round for the
        whole batch (update-topic replay is one UP per factor row)."""
        self.x.set_batch(users, vectors)
        with self._expected_lock:
            self._expected_users.difference_update(users)
        if self._x_staging:
            with self._cache_lock:
                self._x_dirty = True
                self._x_dirty_ids.update(users)

    def set_item_vectors(self, items: list[str], vectors: np.ndarray) -> None:
        self.y.set_batch(items, vectors)
        with self._expected_lock:
            self._expected_items.difference_update(items)
        with self._cache_lock:
            self._y_dirty = True
            self._dirty_ids.update(items)
        self._drop_yty_solver()

    # -- known items (ALSServingModel.java:189-258) --------------------------

    def add_known_items(self, user: str, items: Iterable[str]) -> None:
        items = list(items)
        if not items:
            return
        with self._known_lock.write():
            self._known_items.setdefault(user, set()).update(items)

    def add_known_items_many(self, pairs: Iterable[tuple[str, list[str]]]) -> None:
        """Batched known-items merge under one write lock."""
        with self._known_lock.write():
            known = self._known_items
            for user, items in pairs:
                if items:
                    known.setdefault(user, set()).update(items)

    def get_known_items(self, user: str) -> set[str]:
        with self._known_lock.read():
            return set(self._known_items.get(user, ()))

    def remove_known_item(self, user: str, item: str) -> None:
        with self._known_lock.write():
            s = self._known_items.get(user)
            if s is not None:
                s.discard(item)

    def get_known_item_counts(self) -> dict[str, int]:
        with self._known_lock.read():
            return {u: len(s) for u, s in self._known_items.items()}

    def get_item_counts(self) -> dict[str, int]:
        """item -> number of users that know it, in one locked pass
        (ALSServingModel.getItemCounts analogue)."""
        counts: dict[str, int] = {}
        with self._known_lock.read():
            for items in self._known_items.values():
                for item in items:
                    counts[item] = counts.get(item, 0) + 1
        return counts

    # -- expected-ID accounting ----------------------------------------------

    def set_expected(self, user_ids: Iterable[str], item_ids: Iterable[str]) -> None:
        # computed outside the lock, published under it, so a concurrent
        # set_*_vector's discard can't resurrect an id we just removed
        users = set(user_ids) - set(self.x.ids())
        items = set(item_ids) - set(self.y.ids())
        with self._expected_lock:
            self._expected_users = users - set(self.x.ids())
            self._expected_items = items - set(self.y.ids())

    def get_fraction_loaded(self) -> float:
        with self._expected_lock:
            expected = len(self._expected_users) + len(self._expected_items)
        loaded = self.x.size() + self.y.size()
        if expected + loaded == 0:
            return 1.0
        return loaded / (loaded + expected)

    # -- rotation (retainRecentAnd*: 382-441) --------------------------------

    def retain_recent_and_user_ids(self, ids: set[str]) -> None:
        self.x.retain_recent_and_ids(ids)
        if self._x_staging:
            with self._cache_lock:
                self._x_dirty = True
                # membership may have SHRUNK: staged rows for removed users
                # must stop serving immediately (the vector path would 404),
                # so index submit disables until the rebuild lands — and an
                # in-flight restage built from the PRE-rotation store must
                # be discarded at swap time
                self._x_full_rebuild = True
                self._x_epoch += 1

    def retain_recent_and_item_ids(self, ids: set[str]) -> None:
        self.y.retain_recent_and_ids(ids)
        with self._cache_lock:
            self._y_dirty = True
            self._y_full_rebuild = True  # membership may have shrunk
            self._y_rotation_epoch += 1
        self._drop_yty_solver()  # rotation invalidates the cached YtY

    def retain_recent_and_known_items(self, user_ids: set[str]) -> None:
        with self._known_lock.write():
            for u in [u for u in self._known_items if u not in user_ids]:
                del self._known_items[u]

    # -- solver --------------------------------------------------------------

    def observe_fold_in(self, seconds: float, items: int) -> None:
        """One request's fold-in (endpoints._fold_in): its item look-ups
        and recurrence took ``seconds`` over ``items`` basket items."""
        self._m_foldin_seconds.observe(seconds)
        self._m_foldin_requests.inc()
        self._m_foldin_items.inc(items)

    def _drop_yty_solver(self) -> None:
        """A write to Y drops the cached solver. Called AFTER the write has
        marked the device copy dirty: a build that starts in between then
        refreshes that copy first, and one already under way holds the
        solver lock, so its result is dropped here as soon as it lands."""
        with self._solver_lock:
            self._yty_solver = None

    def get_yty_solver(self) -> Solver | None:
        """The cached solver over YtY, built on first use and after every
        write to Y. Where the device copy holds the item rows as they are
        (``gram_ops.supported``: float32 / bfloat16, one chip or sharded,
        and the plain pair) YtY is one pass over THAT copy, refreshed with
        the pending writes first, so it is the Gram matrix of the very
        matrix the scan scores against; a quantized or IVF copy, and a model
        with no items, take the host store's ``get_vtv``. The solver lock is
        held across the build: only requests that need this solver, and the
        writer that is about to drop it, wait behind it; the device copy's
        own lock is not held while the Gram pass runs."""
        with self._solver_lock:
            if self._yty_solver is None:
                y_mat = self._ensure_y_matrix()[2]
                on_device = y_mat is not None and gram_ops.supported(y_mat)
                if on_device:  # the copy as the pending writes leave it, whatever the refresh interval
                    y_mat = self._ensure_y_matrix(force=True)[2]
                    gram_ops.wait_ready(y_mat)  # an upload still on its way is not the build
                t0 = time.perf_counter()
                if on_device:
                    with profiling.annotate("serving.yty.build", **gram_ops.pass_stats(y_mat)):
                        yty = gram_ops.gram(y_mat)
                else:
                    yty = self.y.get_vtv()
                self._yty_solver = get_solver(yty)
                if yty is not None:
                    self._m_yty_seconds.observe(time.perf_counter() - t0)
                    self._m_yty_builds["device" if on_device else "host"].inc()
            return self._yty_solver

    # -- device-side scoring ---------------------------------------------------

    def _shard_mesh(self):
        """The mesh a shard-items model spreads over (all local devices,
        one ``data`` axis; equal by value at every call), else None."""
        if not self.shard_items:
            return None
        from oryx_tpu.parallel.mesh import get_mesh

        return get_mesh()

    def _try_incremental_refresh(self, dirty: list[str]) -> bool:
        """Scatter-update only the dirty rows of the device-resident Y
        (caller holds the cache lock). Returns False when a full rebuild
        is required: membership shrank, a dirty vector vanished, new ids
        exceed padded capacity, or the LSH host path is active."""
        vals, valid = self.y.get_batch(dirty, dim=self.features)
        if not np.all(valid):
            return False  # a dirty id has no vector anymore
        new_ids = [d for d in dirty if d not in self._y_index]
        if len(self._y_ids) + len(new_ids) > topn_ops.capacity(self._y_matrix):
            # an IVF index with a maintainer attached absorbs the growth:
            # the overlay spills its oldest entries to the compaction
            # queue instead of forcing a request-path re-cluster
            if not (
                isinstance(self._y_matrix, ivf_ops.IVFIndex)
                and self._index_pressure_cb is not None
            ):
                return False
        for d in new_ids:  # append into the padded region
            self._y_index[d] = len(self._y_ids)
            self._y_ids.append(d)
        rows = np.fromiter(
            (self._y_index[d] for d in dirty), dtype=np.int32, count=len(dirty)
        )
        # never raises on overflow: the IVF overlay degrades by spilling
        # its oldest entries to the maintainer's pending queue, so the
        # fold-in path stays O(batch) under any pressure — the background
        # compaction (serving/maintain.py) drains the spill, woken here
        # when the overlay crosses its watermark
        self._y_matrix = topn_ops.update_rows(
            self._y_matrix, rows, vals, n_items=len(self._y_ids)
        )
        cb = self._index_pressure_cb
        if (
            cb is not None
            and isinstance(self._y_matrix, ivf_ops.IVFIndex)
            and ivf_ops.needs_maintenance(self._y_matrix)
        ):
            cb()
        return True

    def _ensure_y_matrix(self, force: bool = False):
        with self._cache_lock:
            now = time.monotonic()
            if self._y_dirty and (force or now - self._y_built_at >= self._refresh_sec):
                dirty = list(self._dirty_ids)
                refreshed = (
                    self._y_matrix is not None
                    and not self._y_full_rebuild
                    and self.lsh is None
                    and bool(dirty)
                    and self._try_incremental_refresh(dirty)
                )
                if not refreshed:
                    ids, mat = self.y.to_matrix()
                    self._y_ids = ids
                    self._y_index = {id_: i for i, id_ in enumerate(ids)}
                    if len(ids):
                        import jax.numpy as jnp

                        dtype = {
                            "bfloat16": jnp.bfloat16,
                            "int8": jnp.int8,
                        }.get(self.score_dtype, jnp.float32)
                        if self.shard_items:
                            self._y_matrix = topn_ops.upload_sharded(
                                mat, self._shard_mesh(), dtype=dtype
                            )
                        elif (
                            self.score_dtype == "int8"
                            and self.lsh is None
                            and ivf_ops.ann_active(len(ids))
                        ):
                            # ANN tier: cluster the rebuilt item matrix
                            # into an IVF routing table. Rebuilds ride the
                            # same MODEL/UP topic path as the exact scan —
                            # in-between fold-ins stay visible through the
                            # index's pending overlay (update_rows above).
                            # With tiering on, the host plane moves into
                            # the HBM->RAM->disk cell store right here.
                            self._y_matrix = ivf_ops.attach_tiered_plane(
                                ivf_ops.build_ivf(mat)
                            )
                        else:
                            self._y_matrix = topn_ops.upload(mat, dtype=dtype)
                    else:
                        self._y_matrix = None
                    profiling.record_device_memory_peak()
                    if self.lsh is not None:
                        self._y_host = mat
                        self._y_partitions = (
                            self.lsh.partitions_for(mat) if len(ids) else None
                        )
                    self._y_full_rebuild = False
                    # id space changed: in-flight compaction snapshots are
                    # now stale and must be discarded at install
                    self._y_build_epoch += 1
                self._dirty_ids.clear()
                self._y_dirty = False
                self._y_built_at = now
            # host/partition arrays are returned under the lock so one
            # request sees one consistent (ids, matrix, partitions) snapshot
            # even if a rebuild swaps them mid-flight
            return (
                self._y_ids,
                self._y_index,
                self._y_matrix,
                self._y_host,
                self._y_partitions,
            )

    def _try_incremental_x_refresh(self, dirty: list[str]) -> bool:
        """Scatter-update the dirty rows of the device-resident X (caller
        holds the cache lock). First-time users APPEND into the padded
        device capacity — a steady trickle of new users must not force a
        full re-upload every refresh tick. False = rebuild required
        (capacity exhausted or a dirty user vanished)."""
        new = [u for u in dirty if u not in self._x_index]
        if len(self._x_ids) + len(new) > self._x_capacity:
            return False
        vals, valid = self.x.get_batch(dirty, dim=self.features)
        if not np.all(valid):
            return False  # a dirty user vanished: membership changed
        for u in new:
            self._x_index[u] = len(self._x_ids)
            self._x_ids.append(u)
        rows = np.fromiter(
            (self._x_index[u] for u in dirty), dtype=np.int32, count=len(dirty)
        )
        self._x_matrix = topn_ops.update_query_rows(self._x_matrix, rows, vals)
        return True

    def _refuse_x_staging(self, rows: int, need: int, budget: int) -> None:
        """All or nothing: a user matrix that does not fit the device's
        budget is not staged, and every known user's request goes by the
        vector path (a store lookup and a [b, features] float32 upload a
        pass) for this model's lifetime."""
        log.warning(
            "user matrix not staged: %d users x %d features ask %d bytes on the device "
            "(25 %% headroom included) and the budget is %d (the device's limit less what "
            "is in use less a reserve of %d); every known user's request goes by the "
            "vector path (gauge serving.users.stage.refused = 1)",
            rows, self.features, need, budget, USER_STAGE_RESERVE_BYTES,
        )
        metrics.registry.gauge("serving.users.stage.refused").set(1)
        metrics.registry.gauge("serving.users.staged-rows").set(0)
        metrics.registry.gauge("serving.users.staged-bytes").set(0)
        with self._cache_lock:
            # flip + drain under the same lock that set_user_vector
            # appends dirty ids under, so no stale dirty set is
            # retained for the model's lifetime after the disable
            self._x_matrix = None
            self._x_capacity = 0
            self._x_staging = False
            self._x_stage_refused = True
            self._x_dirty_ids.clear()
            self._x_dirty = False

    def _stage_devices(self):
        """The devices that each hold the staged user matrix whole."""
        mesh = self._shard_mesh()
        if mesh is not None:
            return list(mesh.devices.flat)
        import jax

        return jax.local_devices()[:1]

    def _rebuild_x_staging(self, pre_dirty: set[str], epoch: int) -> None:
        """Full X restage, run by the triggering request thread OUTSIDE
        the cache lock (reading the store and a potentially multi-GB
        upload must not stall Y scoring); the swap happens under the lock
        and is DISCARDED if a rotation bumped the epoch mid-build (the
        snapshot predates it; the next tick rebuilds from the rotated
        store). Ids written during the build stay dirty and catch up on
        the next refresh tick; incremental scatters are held off while a
        build is in flight so the swap can never clobber one.

        The rows go up in chunks read from the store (``stage_queries``):
        the host never holds the matrix a second time, and the id -> row
        index is built here, not under the lock that item scoring takes."""
        try:
            t0 = time.monotonic()
            ids = self.x.ids()
            n = len(ids)
            index = dict(zip(ids, range(n)))
            # pad capacity so a trickle of new users appends via
            # scatter instead of re-uploading everything
            cap = max(64, int(n * 1.25)) if n else 0
            need = cap * self.features * 4
            # The request that started this thread goes on to upload the
            # item matrix, and the two must not race for the same room.
            # Where the users fit even with a device's whole share of the
            # item matrix still to come (float32 and its padding: the most
            # it can ask; counted twice if it is up already) the staging
            # runs beside that upload; where they do not clearly fit, the
            # item matrix goes first and the budget is read again, exact.
            devices = self._stage_devices()
            budget = user_stage_budget(devices)
            waited = 0.0
            items_to_come = int(self.y.size() * self.features * 4 * 1.1) // len(devices)
            if need > budget - items_to_come:
                waited = time.monotonic()
                self._ensure_y_matrix()
                waited = time.monotonic() - waited  # the item upload's time, not staging's
                budget = user_stage_budget(devices)
            metrics.registry.gauge("serving.users.stage-budget-bytes").set(budget)
            if need > budget:
                self._refuse_x_staging(n, need, budget)
                return
            step = topn_ops.query_chunk_rows(self.features)
            chunks = -(-n // step)

            def rows_of_the_store():
                for lo in range(0, n, step):
                    vals, valid = self.x.get_batch(ids[lo : lo + step], dim=self.features)
                    if not np.all(valid):
                        raise _UserVanished
                    yield vals

            staged = None
            if n:
                with profiling.annotate(
                    "serving.users.stage", rows=n, bytes=need, chunks=chunks
                ):
                    try:
                        staged = topn_ops.stage_queries(
                            rows_of_the_store(), cap, self.features, mesh=self._shard_mesh()
                        )
                    except _UserVanished:
                        # membership shrank under the build: the next tick
                        # restages from the store as it then is
                        return
                    staged.block_until_ready()
            profiling.record_device_memory_peak()
            with self._cache_lock:
                if self._x_epoch != epoch:
                    return  # rotation landed mid-build: discard the snapshot
                self._x_ids = ids
                self._x_index = index
                self._x_matrix = staged
                self._x_capacity = cap
                self._x_full_rebuild = False
                self._x_dirty_ids -= pre_dirty
                self._x_dirty = bool(self._x_dirty_ids)
                self._x_built_at = time.monotonic()
            metrics.registry.gauge("serving.users.stage.refused").set(0)
            metrics.registry.gauge("serving.users.staged-rows").set(n)
            metrics.registry.gauge("serving.users.staged-bytes").set(need)
            seconds = time.monotonic() - t0 - waited
            metrics.registry.histogram("serving.users.stage.seconds").observe(seconds)
            log.info(
                "user matrix staged: %d rows (capacity %d), %d bytes of a budget of %d, "
                "%d chunks, %.2f s", n, cap, need, budget, chunks, seconds,
            )
        finally:
            # under the cache lock: _user_scan_row reads this flag under
            # the lock to decide whether a scatter is safe, and a
            # lock-free flip can let a scatter land mid-swap
            # (oryxlint lockset ORX101 caught the bare write)
            with self._cache_lock:
                self._x_building = False

    def _user_scan_row(self, user: str):
        """(x_matrix, row) for index submit, or (None, None) when the
        user isn't freshly staged. Row resolution happens under the cache
        lock so the row, the matrix snapshot, and the staleness check are
        mutually consistent; a pending full restage serves the vector
        path instead of blocking."""
        rebuild_dirty: set[str] | None = None
        with self._cache_lock:
            now = time.monotonic()
            if self._x_dirty and (now - self._x_built_at >= self._refresh_sec):
                dirty = list(self._x_dirty_ids)
                refreshed = (
                    not self._x_building  # a scatter into the old matrix
                    # would be clobbered by the in-flight restage's swap
                    and self._x_matrix is not None
                    and not self._x_full_rebuild
                    and bool(dirty)
                    and self._try_incremental_x_refresh(dirty)  # ms-scale scatter
                )
                if refreshed:
                    self._x_dirty_ids.clear()
                    self._x_dirty = False
                    self._x_built_at = now
                elif not self._x_building:
                    self._x_building = True
                    rebuild_dirty = set(self._x_dirty_ids)
                    rebuild_epoch = self._x_epoch
                    if self._x_full_rebuild:
                        # after a rotation no row of the old matrix serves
                        # (stale, below): let it go before the restage reads
                        # its budget, or a matrix of several GB is refused
                        # for want of room beside its own predecessor
                        self._x_matrix = None
                        self._x_capacity = 0
            stale = (
                self._x_matrix is None
                or self._x_full_rebuild  # rotation pending: rows may be gone
                or user in self._x_dirty_ids
            )
            row = None if stale else self._x_index.get(user)
            x_mat = self._x_matrix
        if rebuild_dirty is not None:
            # run the restage (to_matrix + up to multi-GB upload) on a
            # daemon thread: the request that trips the refresh tick falls
            # through to the vector path instead of paying seconds of
            # latency; _x_building (set under the lock above) already
            # serializes builds, so at most one thread runs this
            prev = self._x_restage_thread
            if prev is not None:
                # _x_building guarantees the previous restage's body has
                # finished; reap the thread object before replacing it
                prev.join(timeout=5.0)
            t = threading.Thread(
                target=self._rebuild_x_staging,
                args=(rebuild_dirty, rebuild_epoch),
                name="als-x-restage",
                daemon=True,
            )
            self._x_restage_thread = t  # joinable: tests + orderly close
            t.start()
            from oryx_tpu.common import ledger

            ledger.register("thread", t, live=threading.Thread.is_alive)
        if row is None:
            return None, None
        return x_mat, row

    def top_n_for_user(
        self,
        user: str,
        how_many: int,
        exclude: set[str] | None = None,
        rescorer=None,
        cosine: bool = False,
    ) -> list[tuple[str, float]] | None:
        """top_n for a known user id, or None when the user is unknown.

        With the device-resident X enabled (and the exact device scan in
        play), the request ships an int32 row index instead of a query
        vector — the serving twin of ``submit_top_k_multi_indexed``. A
        user whose vector changed since the last X refresh (or isn't
        staged yet) falls back to the fresh host vector, so results are
        never staler than the vector path's."""
        if self._x_staging:
            x_mat, row = self._user_scan_row(user)
            if row is not None:
                ids, _index, y_mat, _h, _p = self._ensure_y_matrix()
                if y_mat is not None:
                    return self._select_loop(
                        ids,
                        len(ids),
                        lambda k: score_indexed_default(
                            y_mat, x_mat, row, k, cosine=cosine
                        ),
                        how_many,
                        exclude,
                        rescorer,
                    )
        vec = self.get_user_vector(user)
        if vec is None:
            return None
        if self._x_staging or self._x_stage_refused:
            # a known user whose row was not there to ship: not staged yet,
            # written since, a restage pending, or the budget refused
            self._unstaged_requests.inc()
        return self.top_n(vec, how_many, exclude=exclude, rescorer=rescorer, cosine=cosine)

    def top_n(
        self,
        query: np.ndarray,
        how_many: int,
        exclude: set[str] | None = None,
        rescorer=None,
        cosine: bool = False,
    ) -> list[tuple[str, float]]:
        """Top-N items by dot (or cosine) score against `query`: one
        batched device matvec + top_k, replacing the reference's
        LSH-partitioned thread-pool scan (ALSServingModel.topN:289-335)."""
        ids, index, y_mat, y_host, y_partitions = self._ensure_y_matrix()
        if y_mat is None:
            return []
        # LSH pruning (sample-rate < 1): only rows whose partition falls in
        # the query's Hamming ball are scored, on host (the approximate
        # CPU-parity path; exact device scan otherwise)
        lsh_rows: np.ndarray | None = None
        if self.lsh is not None and y_partitions is not None:
            cand = self.lsh.candidate_indices(query)
            lsh_rows = np.flatnonzero(np.isin(y_partitions, cand))
            if len(lsh_rows) == 0:
                lsh_rows = None  # degenerate: fall back to the exact scan
        num_candidates = len(lsh_rows) if lsh_rows is not None else len(ids)

        def score_fn(k: int):
            if lsh_rows is not None:
                return _host_top_k(y_host, lsh_rows, query, k, cosine=cosine)
            # continuous batching: concurrent requests against the same
            # Y snapshot coalesce into one device call
            return score_default(y_mat, query, k, cosine=cosine)

        return self._select_loop(
            ids, num_candidates, score_fn, how_many, exclude, rescorer
        )

    @staticmethod
    def _select_loop(
        ids, num_candidates, score_fn, how_many, exclude, rescorer
    ) -> list[tuple[str, float]]:
        """Candidate-window widening shared by the vector and index-submit
        paths: widen until how_many survive filtering or every item has
        been considered (the reference streams all items,
        ALSServingModel.topN:289-335, so filters can never starve
        results)."""
        exclude = exclude or set()
        margin = how_many + len(exclude)
        if rescorer is not None:
            margin = max(margin * 4, margin + 32)  # rescorer may filter many

        def filter_candidates(idx, scores) -> list[tuple[str, float]]:
            out: list[tuple[str, float]] = []
            for i, s in zip(idx, scores):
                if int(i) < 0:
                    # ANN starved-window padding: fewer finite candidates
                    # than k (tiny probed cells); nothing real was dropped
                    continue
                id_ = ids[int(i)]
                if id_ in exclude:
                    continue
                score = float(s)
                if rescorer is not None:
                    if rescorer.is_filtered(id_):
                        continue
                    score = rescorer.rescore(id_, score)
                    if np.isnan(score):
                        continue
                out.append((id_, score))
                if len(out) == how_many and rescorer is None:
                    break
            return out

        while True:
            k = min(margin, num_candidates)
            idx, scores = score_fn(k)
            if rescorer is not None:
                # child of the ambient serving.request span; sibling of
                # the batcher's serving.scan
                with tracing.span("serving.rescore", attrs={"k": int(k)}) as sp:
                    out = filter_candidates(idx, scores)
                    sp.set("kept", len(out))
            else:
                out = filter_candidates(idx, scores)
            if len(out) >= how_many or k >= num_candidates:
                break
            margin = margin * 4
        if rescorer is not None:
            out.sort(key=lambda t: -t[1])
        return out[:how_many]

    # -- ANN maintenance protocol (serving/maintain.py) ----------------------

    def set_index_pressure_callback(self, cb) -> None:
        """Wire the maintainer's wake-up: called (under the cache lock)
        when a fold-in batch crosses the overlay watermark or spills."""
        with self._cache_lock:
            self._index_pressure_cb = cb

    @property
    def index_generation(self) -> str | None:
        """The published index generation this model's layout came from,
        or None when the clustering is locally built."""
        return self._index_generation

    def note_published_index(self, generation_id: str) -> None:
        """This replica just PUBLISHED this generation (its installed
        layout is the generation): dedup the self-delivery off the
        update topic instead of rebuilding from our own centroids."""
        with self._cache_lock:
            self._index_generation = str(generation_id)

    def maintenance_snapshot(self, watermark: float = 0.5, force: bool = False):
        """(index, pending snapshot) for one background compaction pass,
        or None when there is nothing to compact (no IVF index, a forced
        rebuild pending, or overlay pressure below the watermark). The
        snapshot deep-copies the overlay's raw rows under the cache lock
        — O(overlay), never O(catalog) — so compaction runs off-lock
        against stable inputs while fold-ins keep landing."""
        with self._cache_lock:
            idx = self._y_matrix
            if not isinstance(idx, ivf_ops.IVFIndex):
                return None
            if self._y_full_rebuild:
                return None  # rotation owns the next layout
            if not force and not ivf_ops.needs_maintenance(idx, watermark=watermark):
                return None
            snap = ivf_ops.snapshot_pending(idx)
            self._y_snapshot_epoch = self._y_build_epoch
            return idx, snap

    def install_compacted(self, new_index, stats: dict) -> bool:
        """Swap a compacted index in (one pointer write under the cache
        lock). Fold-ins that landed after the snapshot are replayed onto
        the new layout first — detected by comparing each live overlay /
        spill entry's fold-in time against the snapshot's — so no update
        is lost across the swap. Returns False (result discarded) when a
        full rebuild or rotation changed the id space mid-compaction."""
        with self._cache_lock:
            cur = self._y_matrix
            if (
                not isinstance(cur, ivf_ops.IVFIndex)
                or self._y_full_rebuild
                or self._y_build_epoch != self._y_snapshot_epoch
            ):
                return False
            snap_born = stats.get("born") or {}
            feat = cur.features
            replay_ids: list[int] = []
            replay_rows: list[np.ndarray] = []
            if cur.ov_raw_host is not None:
                cur_born = cur.ov_born or {}
                for item, slot in cur.ov_map.items():
                    b = cur_born.get(item, 0.0)
                    if item not in snap_born or b > snap_born[item]:
                        replay_ids.append(int(item))
                        replay_rows.append(cur.ov_raw_host[slot, :feat].copy())
            for item, (raw, b) in (cur.pending_spill or {}).items():
                if item not in snap_born or b > snap_born[item]:
                    replay_ids.append(int(item))
                    replay_rows.append(np.asarray(raw)[:feat].copy())
            if replay_ids:
                new_index = ivf_ops.update_rows(
                    new_index,
                    np.asarray(replay_ids, np.int64),
                    np.stack(replay_rows),
                    n_items=len(self._y_ids),
                )
                stats["replayed"] = len(replay_ids)
            self._y_matrix = new_index
            self._y_snapshot_epoch = -1  # consumed
            return True

    def apply_index_generation(self, ref: str) -> bool:
        """Adopt a published index generation (INDEX-REF): rebuild the
        IVF layout over THIS replica's item store seeded with the
        generation's centroids — same cell geometry fleet-wide without
        shipping item planes — and swap with zero downtime (the build
        runs off-lock; requests keep scanning the old index until one
        pointer write under the cache lock). Returns True on swap."""
        from oryx_tpu.serving import maintain as maintain_mod

        loaded = maintain_mod.read_index_generation(ref)
        if loaded is None:
            return False
        gid, manifest, cents = loaded
        if self._index_generation == gid:
            return False  # duplicate delivery
        if int(manifest.get("features") or cents.shape[1]) != self.features:
            log.warning(
                "index generation %s features mismatch (%s != %d); skipped",
                gid, manifest.get("features"), self.features,
            )
            return False
        if self.lsh is not None or self.shard_items or self.score_dtype != "int8":
            return False  # index generations only drive the IVF scan mode
        with self._cache_lock:
            rot0 = self._y_rotation_epoch
            # ids dirty NOW are covered by the store snapshot below — the
            # build includes their current values, so they stop being
            # dirty once the swap lands (writes racing the build re-dirty)
            dirty0 = set(self._dirty_ids)
        ids, mat = self.y.to_matrix()
        if not ivf_ops.ann_active(len(ids)):
            return False
        new = ivf_ops.attach_tiered_plane(ivf_ops.build_ivf(mat, centroids=cents))
        with self._cache_lock:
            if self._y_rotation_epoch != rot0:
                # a rotation raced the build: its rebuild must win
                # (membership may have shrunk since our store snapshot)
                return False
            self._y_ids = list(ids)
            self._y_index = {id_: i for i, id_ in enumerate(ids)}
            self._y_matrix = new
            # built from the CURRENT store: any pending full rebuild is
            # satisfied by this layout
            self._y_full_rebuild = False
            self._y_build_epoch += 1
            self._y_snapshot_epoch = -1
            self._y_built_at = time.monotonic()
            # ids written after the to_matrix snapshot stay in _dirty_ids:
            # the next refresh tick folds them into the fresh overlay
            self._dirty_ids.difference_update(dirty0)
            self._y_dirty = bool(self._dirty_ids)
            self._index_generation = gid
        return True

    def all_item_ids(self) -> list[str]:
        return self.y.ids()

    def all_user_ids(self) -> list[str]:
        return self.x.ids()

    def close(self) -> None:
        """Orderly teardown: reap the in-flight X restage thread and drop
        the device-resident score matrices so a replaced model (fleet
        rotation, MODEL update with new hyperparams) releases its HBM
        instead of pinning it until GC notices. Idempotent."""
        t = self._x_restage_thread
        if t is not None:
            self._x_restage_thread = None
            t.join(timeout=10.0)
        with self._cache_lock:
            self._y_matrix = None
            self._y_host = None
            self._y_partitions = None
            self._x_matrix = None
            self._x_index = {}
            self._x_ids = []
            # a straggler request still holding this model rebuilds from
            # the vector stores instead of scoring against a dropped cache
            self._y_dirty = True
            self._y_full_rebuild = True
            self._x_dirty = True
            self._x_full_rebuild = True

    def __repr__(self) -> str:  # pragma: no cover
        return f"ALSServingModel[features={self.features}, X={self.x.size()}, Y={self.y.size()}]"


def _host_top_k(
    y_host: np.ndarray,
    rows: np.ndarray,
    query: np.ndarray,
    k: int,
    cosine: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Partial top-k over an LSH-pruned row subset, on host: the scored
    candidate set is already ~sample-rate of the items, so numpy argpartition
    beats a device round-trip at these sizes."""
    sub = y_host[rows]
    scores = sub @ np.asarray(query, dtype=np.float32)
    if cosine:
        qn = float(np.linalg.norm(query))
        norms = np.linalg.norm(sub, axis=1)
        scores = scores / np.maximum(norms * qn, 1e-12)
    k = max(1, min(int(k), len(rows)))
    part = np.argpartition(-scores, k - 1)[:k]
    order = part[np.argsort(-scores[part])]
    return rows[order], scores[order]


class ALSServingModelManager(AbstractServingModelManager):
    """Consume protocol identical to the speed manager plus known-items
    from UP payloads and rescorer loading
    (ALSServingModelManager.java:46-176)."""

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.implicit = config.get_bool("oryx.als.implicit")
        self.no_known_items = config.get_bool("oryx.als.no-known-items")
        self.sample_rate = config.get_float("oryx.als.sample-rate")
        self.score_dtype = config.get_string("oryx.als.serving.score-dtype")
        self.shard_items = config.get_bool("oryx.als.serving.shard-items")
        self.device_user_matrix = config.get_bool(
            "oryx.als.serving.device-user-matrix"
        )
        if self.score_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"oryx.als.serving.score-dtype must be float32, bfloat16, or "
                f"int8, got {self.score_dtype!r}"
            )
        self.rescorer_provider = _load_rescorer_providers(config)
        self.model: ALSServingModel | None = None
        self._consumed = 0

    def consume_blocks(self, block_iterator) -> None:
        """Columnar consume: contiguous "UP" runs parse vectorized and
        apply via the batched setters (replay of a factor publish is one
        UP per row — a million-record startup replay). X rows carrying
        known-item lists parse those too; anything escaped or unusual
        falls back to per-record consume in order."""
        consume_blocks_columnar(
            block_iterator,
            lambda: self.model is not None,
            self._apply_up_batch,
            self.consume,
        )

    def _apply_up_batch(self, lines: list[bytes]) -> None:
        model = self.model
        applied = apply_up_lines(
            lines,
            model.features,
            model.set_user_vectors,
            model.set_item_vectors,
            lambda km: self.consume(iter([km])),
            on_known=(
                None
                if self.no_known_items
                else lambda pairs: model.add_known_items_many(pairs)
            ),
            strict_tail=True,  # the known list is part of the wire contract
        )
        self._consumed += applied  # slow path self-counts

    def consume(self, update_iterator: Iterator[KeyMessage]) -> None:
        for km in update_iterator:
            key, message = km.key, km.message
            if key == "UP":
                if self.model is None:
                    continue
                update = read_json(message)
                which, id_ = update[0], str(update[1])
                vector = np.asarray(update[2], dtype=np.float32)
                if which == "X":
                    self.model.set_user_vector(id_, vector)
                    if len(update) > 3 and not self.no_known_items:
                        self.model.add_known_items(id_, [str(i) for i in update[3]])
                elif which == "Y":
                    self.model.set_item_vector(id_, vector)
            elif key in ("MODEL", "MODEL-REF"):
                pmml = app_pmml.read_pmml_from_update_message(key, message)
                if pmml is None:
                    log.warning("dropped unreadable model update")
                    continue
                features = int(app_pmml.get_required_extension_value(pmml, "features"))
                implicit = app_pmml.get_required_extension_value(pmml, "implicit") == "true"
                x_ids = set(app_pmml.get_extension_content(pmml, "XIDs") or [])
                y_ids = set(app_pmml.get_extension_content(pmml, "YIDs") or [])
                if (
                    self.model is None
                    or self.model.features != features
                    or self.model.implicit != implicit
                ):
                    old = self.model
                    self.model = ALSServingModel(
                        features,
                        implicit,
                        sample_rate=self.sample_rate,
                        score_dtype=self.score_dtype,
                        shard_items=self.shard_items,
                        device_user_matrix=self.device_user_matrix,
                    )
                    self.model.set_expected(x_ids, y_ids)
                    if old is not None:
                        # requests racing the swap hold their own model ref
                        # (get_model snapshots); teardown only reaps the
                        # restage thread and drops device matrices
                        old.close()
                else:
                    self.model.retain_recent_and_user_ids(x_ids)
                    self.model.retain_recent_and_item_ids(y_ids)
                    self.model.retain_recent_and_known_items(
                        x_ids | set(self.model.all_user_ids())
                    )
                    self.model.set_expected(x_ids, y_ids)
            elif key == "INDEX-REF":
                # ANN index generation (serving/maintain.py): rebuild this
                # replica's IVF layout seeded with the published centroids
                # and swap with zero downtime; unusable refs are dropped
                # (the local layout keeps serving)
                if self.model is not None:
                    try:
                        self.model.apply_index_generation(message)
                    except Exception:
                        log.warning(
                            "dropped unusable index generation %r", message,
                            exc_info=True,
                        )
            else:
                raise ValueError(f"bad key {key}")
            self._consumed += 1
            if self._consumed % 10_000 == 0:
                log.info("%s updates consumed; model: %r", self._consumed, self.model)

    def get_model(self) -> ALSServingModel | None:
        return self.model

    def close(self) -> None:
        model, self.model = self.model, None
        if model is not None:
            model.close()


def _load_rescorer_providers(config: Config):
    """Load RescorerProvider chain from oryx.als.rescorer-provider-class
    (ALSServingModelManager.java:141-174)."""
    names = config.get_optional_strings("oryx.als.rescorer-provider-class")
    if not names:
        return None
    from oryx_tpu.app.als.rescorer import MultiRescorerProvider
    from oryx_tpu.common.lang import load_instance_of

    providers = [load_instance_of(n) for n in names]
    if len(providers) == 1:
        return providers[0]
    return MultiRescorerProvider(providers)
