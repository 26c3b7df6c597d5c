"""IVF approximate-retrieval tier for the serving scan.

The exact quantized scan (docs/serving-scan.md) streams every item row per
query, which caps single-chip serving near 1M items. This module turns
that ceiling into a 10-100M-item story with the classic inverted-file
(Faiss-style) two-stage retrieval, built entirely from machinery already
in the repo:

1. **Coarse quantizer** — the item matrix is clustered into ~sqrt(n)
   cells with ``ops/kmeans.py`` (k-means|| init + mini-batch Lloyd); each
   item is assigned to its nearest centroid.
2. **Cell-contiguous layout** — items are permuted so every cell occupies
   a contiguous, tile-aligned run of the same two-plane int8 codes the
   exact scan uses (``StreamingItemMatrix``'s per-row quantization rules
   verbatim, so each item's codes are bit-identical to a fresh
   ``upload``). The primary plane is additionally stored ITEM-major: a
   probed run is then a contiguous byte range, which is what makes the
   cell scan a dense GEMM instead of a strided gather (a feature-major
   gather pulls one cacheline per byte — measured 25x slower).
3. **Routing** — a query dots against the [feat, n_cells] centroid matrix
   and keeps the top ``nprobe`` cells.
4. **Probed scan + exact rescore** — a query group's probed cells union
   into a tile list; each tile is one contiguous ``dynamic_slice`` +
   plane-1 GEMM reduced to per-chunk maxes (the same chunk-max ranking
   the exact scan uses), and the top chunks then rescore through the
   same ``pallas_topn._gathered_pair_scores`` two-plane epilogue as the
   exact path's candidate tail. Scanning the group UNION means every
   query sees a superset of its own probed cells — recall only goes up —
   while the int8->f32 tile conversion amortizes across the group.

Speed-layer visibility: ``update_rows`` keeps fold-ins visible through
the ANN path with a **pending-overlay list** — touched rows leave the
cell structure (their slot id is tombstoned) and land in a small
device-resident overlay of dequantized rows that every query scans
exactly and merges before the final top-k. The overlay holds the rows'
two-plane DEQUANTIZED values, so overlay scores match a fresh upload's
quantized scores to f32 rounding. A full overlay never stalls the
request path: the OLDEST overlay entries spill to a host-side pending
queue (``pending_spill``) and their slots are reused — spilled rows go
invisible until the next compaction folds them back into the clustered
layout, a bounded-freshness trade instead of the old synchronous
full re-cluster (:class:`IVFOverlayFull` is kept for compatibility but
no longer raised here).

Maintenance: ``compact_ivf`` folds the overlay + spill queue back into
the cell-contiguous layout WITHOUT retraining the coarse quantizer —
retained rows keep their quantized codes verbatim (per-row quantization
is deterministic, so the compacted planes are bit-identical to a
from-scratch build over the same item set), tombstoned slots are
garbage-collected, oversized cells split via a local 2-means and
undersized cells merge into their nearest surviving neighbour
(SPFresh-style LIRE rebalancing, DiskANN-style background rebuild).
``oryx_tpu/serving/maintain.py`` drives it off the request path.

Exactness contract: with ``nprobe >= n_cells`` every cell is probed, the
candidate set is the whole catalog ordered by ascending item id, and the
scores come from the shared epilogue on the SAME feature-major planes —
the result reproduces the exact int8 scan's top-N bit-for-bit (tested in
tests/ops/test_ivf_scan.py; the item-major plane exists only for stage-1
ranking, whose rounding never touches the returned scores).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.ops import pallas_topn as pt

# -- knobs (oryx.serving.scan.ann.*, pushed by ServingLayer) ------------------

# master switch for the serving tier (ops-level entry points work either way)
ANN_ENABLED = False
# coarse cells; 0 = auto round(sqrt(n))
N_CELLS = 0
# cells probed per query; 0 = derive from PROBE_FRACTION
NPROBE = 0
# fraction of items a query should scan when NPROBE is 0 (nprobe =
# round(fraction * n_cells)); the knob tools/load_benchmark.py maps the
# reference harness's LSH sampleRate onto. 1% probes measure recall@10
# ~0.997 on clustered catalogs at 200k-1M items (see docs/serving-scan.md
# for the recall/latency trade-off and the data-model caveat)
PROBE_FRACTION = 0.01
# catalogs below this stay on the exact scan (clustering overhead isn't
# worth it when one GEMM streams the whole matrix)
MIN_ITEMS = 100_000
# pending-overlay rows (speed-layer updates between index rebuilds)
OVERLAY_CAPACITY = 4096
# queries per scan group: the probed-cell UNION of a group shares one
# pass of tile gather + GEMM, so bigger groups amortize memory traffic
# but inflate the union (more cells scanned per query); 4-8 measures
# best on the host stage-1 path, where the take is already memcpy-fast
QUERY_BLOCK = 8
# chunks per scan tile: tiles are the dynamic_slice granularity of the
# probed scan, and cells pad to a tile multiple — bigger tiles mean
# fewer, beefier GEMM steps but more padding per cell
TILE_CHUNKS = 8
# None = auto (on for the CPU backend): keep a host-resident dequantized
# f32 copy of the item planes and run the probed scan through numpy
# block-take + BLAS. XLA:CPU gathers byte-at-a-time (~0.4 GB/s measured)
# and converts int8->f32 at ~0.5 Gelem/s, so the device probed path
# loses its sublinearity to data movement; numpy block-take runs at
# memcpy speed and the f32 plane never converts at query time. Costs
# 4x the primary plane's bytes in HOST memory (10 GB at 10M x 256).
HOST_STAGE1 = None

# rows assigned to centroids per jitted block during build
_ASSIGN_BLOCK = 65536


def configure_ann(
    enabled=None,
    cells=None,
    nprobe=None,
    probe_fraction=None,
    min_items=None,
    overlay_capacity=None,
    query_block=None,
    tile_chunks=None,
    host_stage1=None,
):
    """Set the IVF defaults (config: oryx.serving.scan.ann.*). Call
    before the first dispatch — jitted programs bake the derived static
    shapes in at trace time, and the host stage-1 plane only
    materializes at build time."""
    global ANN_ENABLED, N_CELLS, NPROBE, PROBE_FRACTION
    global MIN_ITEMS, OVERLAY_CAPACITY, QUERY_BLOCK, TILE_CHUNKS, HOST_STAGE1
    if enabled is not None:
        ANN_ENABLED = bool(enabled)
    if cells is not None:
        N_CELLS = int(cells)
    if nprobe is not None:
        NPROBE = int(nprobe)
    if probe_fraction is not None:
        PROBE_FRACTION = float(probe_fraction)
    if min_items is not None:
        MIN_ITEMS = int(min_items)
    if overlay_capacity is not None:
        OVERLAY_CAPACITY = int(overlay_capacity)
    if query_block is not None:
        QUERY_BLOCK = int(query_block)
    if tile_chunks is not None:
        TILE_CHUNKS = int(tile_chunks)
    if host_stage1 is not None:
        HOST_STAGE1 = bool(host_stage1)


def _host_stage1_active() -> bool:
    if HOST_STAGE1 is not None:
        return HOST_STAGE1
    return jax.default_backend() == "cpu"


def ann_active(n_items: int) -> bool:
    """Should the serving tier route this catalog through IVF?"""
    return ANN_ENABLED and n_items >= MIN_ITEMS


class IVFOverlayFull(RuntimeError):
    """The pending-overlay list is out of slots.

    Kept for API compatibility: since the spill queue landed,
    ``update_rows`` degrades by spilling the oldest overlay entries to
    ``pending_spill`` instead of raising — no caller sees this on the
    request path anymore. Compaction (``compact_ivf``) drains the queue.
    """


@dataclasses.dataclass(frozen=True, eq=False)
class IVFIndex:
    """Cell-contiguous two-plane int8 item matrix + routing table.

    Device arrays are immutable; ``update_rows`` returns a new handle
    (sharing unchanged planes). The host-side routing tables
    (``id_to_slot``, ``ov_map``) are bookkeeping for the update path and
    are mutated in place under the caller's serialization (the serving
    model updates under its cache lock), never read at query time.

    The slot space ends with one all-padding guard tile (slot ids -1,
    zero codes): tile/chunk selections that have nothing real to point
    at aim there, so downstream gathers always hit masked slots instead
    of a neighbouring cell's items (which would duplicate results).
    """

    # permuted, per-cell tile-padded planes in the exact scan's
    # feature-major layout; padding slots carry scale 1 / codes 0
    mat_t: jax.Array  # [kf_pad, n_slots] int8
    resid: jax.Array  # [kf_pad, n_slots] int8
    # item-major copy of the PRIMARY plane for the dense probed scan
    mat_rows: jax.Array  # [n_slots, kf_pad] int8
    scales: jax.Array  # [1, n_slots] f32
    resid_scales: jax.Array  # [1, n_slots] f32
    norms: jax.Array  # [1, n_slots] f32 (original f32 row norms)
    # slot -> original item id; -1 = padding or superseded by the overlay
    slot_ids: jax.Array  # [n_slots] int32
    # routing table
    centroids_t: jax.Array  # [kf_pad, n_cells] f32
    centroid_norms: jax.Array  # [n_cells] f32
    chunk_start: jax.Array  # [n_cells] int32, in chunk units
    chunk_count: jax.Array  # [n_cells] int32 (occupied chunks only)
    # pending overlay: dequantized rows of updated items, scanned exactly
    ov_rows: jax.Array  # [cap, kf_pad] f32
    ov_ids: jax.Array  # [cap] int32, -1 = empty
    ov_norms: jax.Array  # [cap] f32
    n_items: int
    features: int  # true feature count before int8 sublane padding
    chunk: int  # items per candidate chunk (layout constant)
    tile_chunks: int  # chunks per scan tile (layout constant)
    # host-side routing/update bookkeeping
    chunk_count_host: np.ndarray  # [n_cells] int64
    tile_start_host: np.ndarray  # [n_cells] int64, in tile units
    tile_count_host: np.ndarray  # [n_cells] int64
    id_to_slot: np.ndarray  # [n_items at build] int32, -1 = overlay/none
    ov_map: dict  # item id -> overlay slot
    ov_used: int
    # host stage-1 mirrors (None when HOST_STAGE1 resolves off): the
    # dequantized two-plane f32 item rows (q1*s1 + q2*s2), scanned by
    # numpy block-take + BLAS on the CPU backend; same quantized values
    # as the device planes, so recall and scores match to f32 rounding
    host_plane: np.ndarray | None = None  # [n_slots, kf_pad] f32
    slot_ids_host: np.ndarray | None = None  # [n_slots] int32
    norms_host: np.ndarray | None = None  # [n_slots] f32
    ov_rows_host: np.ndarray | None = None  # [cap, kf_pad] f32
    ov_ids_host: np.ndarray | None = None  # [cap] int32
    ov_norms_host: np.ndarray | None = None  # [cap] f32
    # maintenance bookkeeping (host-side, mutated in place under the
    # caller's serialization, like ov_map):
    # RAW pre-quantization values of each overlay slot — compaction
    # requantizes from these so the compacted codes are bit-identical to
    # a from-scratch build over the same item set (requantizing the
    # DEQUANTIZED overlay values would shift the per-row scale)
    ov_raw_host: np.ndarray | None = None  # [cap, kf_pad] f32
    # item id -> fold-in wall-clock seconds (freshness accounting)
    ov_born: dict | None = None
    # overlay-overflow spill queue: item id -> (raw row [kf_pad] f32,
    # born seconds). Spilled rows are INVISIBLE to queries until the
    # next compaction folds them back in — the bounded-freshness degrade
    # that replaced the request-path full re-cluster.
    pending_spill: dict | None = None
    # optional tiered host plane (native/store.py TieredHostPlane): when
    # set, host stage-1 gathers probed tiles through the HBM->RAM->disk
    # cell store instead of the flat host_plane array
    tier: object | None = None

    @property
    def n_cells(self) -> int:
        return self.centroids_t.shape[1]

    @property
    def n_slots(self) -> int:
        return self.mat_t.shape[1]

    @property
    def num_features(self) -> int:
        return self.features

    @property
    def quantized(self) -> bool:
        return True

    def resolve_nprobe(self, nprobe: int | None = None) -> int:
        """Probed cells per query: explicit arg > NPROBE knob > fraction."""
        p = nprobe if nprobe is not None else NPROBE
        if not p:
            p = int(round(PROBE_FRACTION * self.n_cells))
        return max(1, min(int(p), self.n_cells))

    def prefetch_for_queries(
        self, queries, nprobe: int | None = None, cosine: bool = False
    ) -> int:
        """Advisory async prefetch of the cells these queries will probe.

        The batcher calls this while a scan group assembles (ahead of the
        actual dispatch), so the tier store's disk->RAM copies overlap
        with batching + routing instead of stalling the scan. Routing
        here is a host-side numpy dot (exactness is irrelevant for a
        prefetch hint; the scan re-routes on device). No-op without an
        attached tier. Returns the number of cells hinted."""
        tier = self.tier
        if tier is None:
            return 0
        np_ = self.resolve_nprobe(nprobe)
        if np_ >= self.n_cells:
            return 0  # full probe touches everything; nothing to target
        q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        cent, cnorms = tier.routing_arrays()
        qpad = np.zeros((q.shape[0], cent.shape[0]), np.float32)
        qpad[:, : q.shape[1]] = q
        sc = qpad @ cent
        if cosine:
            sc = sc / np.maximum(cnorms[None, :], 1e-12)
        if np_ < sc.shape[1]:
            part = np.argpartition(-sc, np_ - 1, axis=1)[:, :np_]
        else:
            part = np.broadcast_to(np.arange(sc.shape[1]), sc.shape)
        hinted = np.unique(part)
        tier.prefetch_cells(hinted)
        return int(len(hinted))


# -- build --------------------------------------------------------------------


@jax.jit
def _assign_block_dev(blk, cent_t, half_c2):
    # nearest centroid by L2 == argmax(y.c - ||c||^2/2); HIGHEST so
    # borderline assignments match the kmeans trainer's f32 distances
    s = (
        jnp.dot(
            blk,
            cent_t,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        - half_c2
    )
    return jnp.argmin(-s, axis=1).astype(jnp.int32)


def _assign_cells(mat: np.ndarray, centers: np.ndarray) -> np.ndarray:
    """Nearest-centroid id per item row, in fixed-shape device blocks."""
    n = len(mat)
    cent_t = jnp.asarray(centers.T)
    half = jnp.asarray(0.5 * np.einsum("kd,kd->k", centers, centers)[None, :])
    out = np.empty(n, np.int32)
    block = min(_ASSIGN_BLOCK, n)
    for beg in range(0, n, block):
        sub = np.asarray(mat[beg : beg + block], dtype=np.float32)
        real = len(sub)
        if real < block:  # pad the tail so two shapes compile, not many
            sub = np.concatenate([sub, np.zeros((block - real, sub.shape[1]), np.float32)])
        out[beg : beg + real] = np.asarray(
            _assign_block_dev(jnp.asarray(sub), cent_t, half)
        )[:real]
    return out


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def build_ivf(
    matrix: np.ndarray,
    *,
    n_cells: int | None = None,
    seed: int = 0,
    train_sample: int = 200_000,
    iterations: int = 8,
    overlay_capacity: int | None = None,
    centroids: np.ndarray | None = None,
) -> IVFIndex:
    """Cluster, permute cell-contiguous, quantize, and ship to device.

    The coarse quantizer trains on a uniform sample (mini-batch Lloyd
    over k-means|| seeds); the full catalog then assigns to the trained
    centroids in device blocks. Rows quantize with the exact scan's
    per-row rules, streamed in million-row slices so the host transient
    stays bounded at 10M+ items.

    ``centroids`` short-circuits the training: the catalog lays out onto
    the GIVEN [cells, feat] coarse quantizer (assignment + layout only,
    no Lloyd iterations). This is how a replica swaps onto a published
    index generation — every replica reproduces the maintainer's
    clustering over its own item store without re-running kmeans.
    """
    mat = np.asarray(matrix, dtype=np.float32)
    n, feat = mat.shape
    if n == 0:
        raise ValueError("cannot build an IVF index over zero items")
    chunk = max(8, int(pt._CHUNK))
    tile_chunks = max(1, TILE_CHUNKS)
    tile_slots = tile_chunks * chunk
    if centroids is not None:
        centers = np.ascontiguousarray(centroids, dtype=np.float32)[:, :feat]
        cells = len(centers)
    else:
        cells = int(
            n_cells if n_cells is not None else (N_CELLS or round(math.sqrt(n)))
        )
        cells = max(1, min(cells, n))

        from oryx_tpu.ops.kmeans import train_kmeans

        rng = np.random.default_rng(seed)
        sample = (
            mat[rng.choice(n, train_sample, replace=False)]
            if n > train_sample
            else mat
        )
        minibatch = 32_768 if len(sample) > 65_536 else None
        centers, _counts, _cost = train_kmeans(
            sample,
            cells,
            iterations=iterations,
            init="k-means||",
            seed=seed,
            minibatch_size=minibatch,
        )
        centers = np.asarray(centers, dtype=np.float32)

    assign = _assign_cells(mat, centers)
    order = np.argsort(assign, kind="stable")  # within-cell: ascending id
    counts = np.bincount(assign, minlength=cells).astype(np.int64)
    chunk_counts = -(-counts // chunk)  # occupied chunks; empty cells keep 0
    tile_counts = -(-chunk_counts // tile_chunks)
    spans = tile_counts * tile_slots  # per-cell slot span, tile-aligned
    item_starts = np.zeros(cells + 1, np.int64)
    np.cumsum(counts, out=item_starts[1:])
    slot_base = np.zeros(cells + 1, np.int64)
    np.cumsum(spans, out=slot_base[1:])
    # +1 guard tile at the end: the all-padding landing zone for starved
    # tile/chunk selections
    n_slots = int(slot_base[-1]) + tile_slots
    # slot of the i-th cell-sorted item: its cell's base + rank in cell
    pos_in_cell = np.arange(n, dtype=np.int64) - np.repeat(item_starts[:-1], counts)
    slots_sorted = np.repeat(slot_base[:-1], counts) + pos_in_cell

    kf_pad = pt._ceil_to(feat, pt._INT8_FEAT_MULTIPLE)
    mat_t = np.zeros((kf_pad, n_slots), np.int8)
    resid = np.zeros((kf_pad, n_slots), np.int8)
    mat_rows = np.zeros((n_slots, kf_pad), np.int8)
    scales = np.ones((1, n_slots), np.float32)  # 1.0: padding dequant is a no-op
    rscales = np.ones((1, n_slots), np.float32)
    norms = np.zeros((1, n_slots), np.float32)
    slot_ids = np.full(n_slots, -1, np.int32)
    slot_ids[slots_sorted] = order
    id_to_slot = np.empty(n, np.int32)
    id_to_slot[order] = slots_sorted.astype(np.int32)
    host1 = _host_stage1_active()
    host_plane = np.zeros((n_slots, kf_pad), np.float32) if host1 else None
    slice_rows = 1_000_000  # bounds the quantize transient at 10M+ items
    for beg in range(0, n, slice_rows):
        rows = order[beg : beg + slice_rows]
        sl = slots_sorted[beg : beg + slice_rows]
        sub = mat[rows]
        q, s = pt._quantize_rows(sub)
        q2, s2 = pt._quantize_residual(sub, q, s)
        mat_t[:feat, sl] = q.T
        resid[:feat, sl] = q2.T
        mat_rows[sl, :feat] = q
        scales[0, sl] = s
        rscales[0, sl] = s2
        norms[0, sl] = np.linalg.norm(sub, axis=1)
        if host_plane is not None:
            host_plane[sl, :feat] = (
                q.astype(np.float32) * s[:, None]
                + q2.astype(np.float32) * s2[:, None]
            )

    cent_t = np.zeros((kf_pad, cells), np.float32)
    cent_t[:feat] = centers.T
    cap = _pow2_ceil(overlay_capacity or OVERLAY_CAPACITY)

    return IVFIndex(
        mat_t=jnp.asarray(mat_t),
        resid=jnp.asarray(resid),
        mat_rows=jnp.asarray(mat_rows),
        scales=jnp.asarray(scales),
        resid_scales=jnp.asarray(rscales),
        norms=jnp.asarray(norms),
        slot_ids=jnp.asarray(slot_ids),
        centroids_t=jnp.asarray(cent_t),
        centroid_norms=jnp.asarray(np.linalg.norm(centers, axis=1)),
        chunk_start=jnp.asarray((slot_base[:-1] // chunk).astype(np.int32)),
        chunk_count=jnp.asarray(chunk_counts.astype(np.int32)),
        ov_rows=jnp.zeros((cap, kf_pad), jnp.float32),
        ov_ids=jnp.full((cap,), -1, jnp.int32),
        ov_norms=jnp.zeros((cap,), jnp.float32),
        n_items=n,
        features=feat,
        chunk=chunk,
        tile_chunks=tile_chunks,
        chunk_count_host=chunk_counts,
        tile_start_host=slot_base[:-1] // tile_slots,
        tile_count_host=tile_counts,
        id_to_slot=id_to_slot,
        ov_map={},
        ov_used=0,
        host_plane=host_plane,
        slot_ids_host=slot_ids.copy() if host1 else None,
        norms_host=norms[0].copy() if host1 else None,
        ov_rows_host=np.zeros((cap, kf_pad), np.float32) if host1 else None,
        ov_ids_host=np.full((cap,), -1, np.int32) if host1 else None,
        ov_norms_host=np.zeros((cap,), np.float32) if host1 else None,
        ov_raw_host=np.zeros((cap, kf_pad), np.float32),
        ov_born={},
        pending_spill={},
    )


# -- query: routing -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("nprobe", "cosine"))
def _route_cells(cent_t, cnorms, chunk_count, q_bf, *, nprobe, cosine):
    route = jnp.dot(
        q_bf,
        cent_t,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if cosine:
        # ||q|| is constant per row: dividing by centroid norms alone
        # preserves the per-query cosine routing order
        route = route / jnp.maximum(cnorms[None, :], 1e-12)
    # a cell that holds nothing is probed last: k-means seeds that fell on
    # duplicate rows leave empty cells whose centroid equals an occupied
    # cell's, and a probe spent on one is a probe the occupied cell loses
    route = jnp.where(chunk_count[None, :] > 0, route, -jnp.inf)
    _, cells = jax.lax.top_k(route, nprobe)
    return cells  # [b, nprobe]


def _group_tile_lists(index: IVFIndex, cells_np: np.ndarray, g: int):
    """Union each query group's probed cells into ragged tile lists.

    Scanning the union instead of per-query lists keeps the scan dense
    and uniform — a query only ever sees EXTRA cells, never fewer.
    """
    b = cells_np.shape[0]
    groups = -(-b // g)
    per_group = []
    for gi in range(groups):
        uc = np.unique(cells_np[gi * g : (gi + 1) * g].ravel())
        cnt = index.tile_count_host[uc]
        uc = uc[cnt > 0]  # empty cells contribute no tiles
        cnt = index.tile_count_host[uc]
        starts = index.tile_start_host[uc]
        total = int(cnt.sum())
        if total == 0:
            per_group.append(np.empty(0, np.int64))
            continue
        # ragged [start, start+cnt) ranges flattened in one vector op
        base = np.repeat(starts, cnt)
        cum = np.zeros(len(uc) + 1, np.int64)
        np.cumsum(cnt, out=cum[1:])
        per_group.append(base + (np.arange(total) - np.repeat(cum[:-1], cnt)))
    return per_group


def _pack_tiles(index: IVFIndex, lists, e: int):
    """Stack ragged tile lists into a [len(lists), e] device array; short
    lists pad with the guard tile, whose slots are all masked."""
    guard = index.n_slots // (index.tile_chunks * index.chunk) - 1
    tiles = np.full((len(lists), e), guard, np.int64)
    for gi, t in enumerate(lists):
        tiles[gi, : len(t)] = t
    return jnp.asarray(tiles.astype(np.int32))


# -- query: host stage-1 path (CPU backend) -----------------------------------


def _host_topk(index: IVFIndex, qpad: np.ndarray, cells: np.ndarray, k: int, cosine: bool):
    """Probed scan over the host-resident dequantized f32 plane.

    One numpy pass per query group: block-take the group's probed tiles
    (memcpy-speed, unlike XLA:CPU's elementwise gather), one BLAS GEMM
    against the group's queries, then a per-query partition + (score
    desc, id asc) ordering — the same tie direction as the exact scan's
    ascending-id stable top_k. Because the plane holds the two-plane
    DEQUANTIZED values, the ranking scores ARE final-precision scores:
    the CPU path collapses the rescore stage instead of re-gathering
    candidates through XLA. Returns host (vals [n, k] f32, ids [n, k]
    int32); the overlay merges from its host mirror.
    """
    n, kf = qpad.shape
    kk = max(1, int(k))
    g = max(1, min(QUERY_BLOCK, n))
    # probe-locality sort (see top_k_device): shared cells collapse in
    # the group union
    order = np.argsort(cells[:, 0], kind="stable")
    lists = _group_tile_lists(index, cells[order], g)
    ts = index.tile_chunks * index.chunk
    n_tiles = index.n_slots // ts
    # tiered plane: probed tiles gather through the HBM->RAM->disk cell
    # store (promotion + residency tracked there); the flat array path
    # stays the default. slot ids / norms are 8 B/slot — always RAM.
    tier = index.tier
    plane3 = None if tier is not None else index.host_plane.reshape(n_tiles, ts, kf)
    sids3 = index.slot_ids_host.reshape(n_tiles, ts)
    norms3 = index.norms_host.reshape(n_tiles, ts)
    used = index.ov_used
    qn = np.linalg.norm(qpad, axis=1) if cosine else None
    if used:
        ov_sc = qpad @ index.ov_rows_host[:used].T  # [n, used] exact
        if cosine:
            ov_sc = ov_sc / np.maximum(
                index.ov_norms_host[None, :used] * qn[:, None], 1e-12
            )
        ov_ids = index.ov_ids_host[:used].astype(np.int64)
    out_v = np.full((n, kk), -np.inf, np.float32)
    out_i = np.full((n, kk), -1, np.int32)
    for gi, tl in enumerate(lists):
        rows = order[gi * g : (gi + 1) * g]
        qg = qpad[rows]
        if len(tl):
            if tier is not None:
                slab = tier.gather_tiles(tl)  # [len(tl)*ts, kf] f32
            else:
                slab = plane3[tl].reshape(-1, kf)  # contiguous block take
            sc = slab @ qg.T  # [S, group] final-precision scores
            ssid = sids3[tl].reshape(-1).astype(np.int64)
            if cosine:
                nr = norms3[tl].reshape(-1)
                sc = sc / np.maximum(nr[:, None] * qn[rows][None, :], 1e-12)
            sc[ssid < 0, :] = -np.inf  # padding + tombstoned slots
        else:  # every probed cell was empty: overlay-only candidates
            sc = np.empty((0, len(rows)), np.float32)
            ssid = np.empty(0, np.int64)
        kp = min(kk, sc.shape[0])
        if kp and sc.shape[0] > kp:
            part = np.argpartition(-sc, kp - 1, axis=0)[:kp]  # [kp, group]
        else:
            part = np.broadcast_to(
                np.arange(sc.shape[0])[:, None], (sc.shape[0], len(rows))
            )
        for j, qi in enumerate(rows):
            pv = sc[part[:, j], j]
            pi = ssid[part[:, j]]
            if used:
                pv = np.concatenate([pv, ov_sc[qi]])
                pi = np.concatenate([pi, ov_ids])
            if not len(pv):
                continue
            # score desc, item id asc — the exact path's tie direction
            o = np.lexsort((pi, -pv))[:kk]
            pv, pi = pv[o], pi[o]
            fin = np.isfinite(pv)
            out_v[qi, : len(pv)] = np.where(fin, pv, -np.inf)
            out_i[qi, : len(pv)] = np.where(fin, pi, -1).astype(np.int32)
    return out_v, out_i


# -- query: probed scan + exact rescore ---------------------------------------


@functools.partial(
    jax.jit, static_argnames=("k", "kc", "tile", "chunk", "cosine")
)
def _probe_topk(
    mat_rows,
    mat_t,
    resid,
    scales,
    resid_scales,
    norms,
    slot_ids,
    ov_rows,
    ov_ids,
    ov_norms,
    q_gbf,
    tiles_ge,
    *,
    k,
    kc,
    tile,
    chunk,
    cosine,
):
    """[G, g, kf] query groups x [G, E] probed tiles -> (vals, ids) [G, g, k].

    Stage 1 is the exact scan's chunk-max ranking restricted to the
    probed tiles: a group's tile list gathers as one contiguous-block
    slab of the item-major primary plane, so the whole probed region is
    ONE int8->f32 conversion + GEMM shared by the query group, reduced
    to per-chunk maxes in the epilogue. (One big step per group, not one
    small step per tile — XLA:CPU charges ~100us of dispatch per scan
    step, which at thousands of tiles costs more than the math.)
    Stage 2 takes each query's top ``kc`` chunks and rescores their items
    through the same two-plane gather epilogue as the exact path's
    candidate tail, then merges the pending overlay's exact scores."""
    n_slots = mat_rows.shape[0]
    kf = mat_rows.shape[1]
    guard_chunk = n_slots // chunk - 1  # inside the guard tile: all masked
    tile_slots = tile * chunk
    n_tiles = n_slots // tile_slots
    # tile-blocked views: row-major reshapes, no data movement
    rows3 = mat_rows.reshape(n_tiles, tile_slots, kf)
    scales_t = scales.reshape(n_tiles, tile_slots)
    sids_t = slot_ids.reshape(n_tiles, tile_slots)
    norms_t = norms.reshape(n_tiles, tile_slots)

    def one(args):
        q, tl = args  # [g, kf], [E]
        g = q.shape[0]
        e = tl.shape[0]
        qn = jnp.linalg.norm(q, axis=1, keepdims=True) if cosine else None
        qt = q.T  # [kf, g]

        # contiguous-block gather of the probed tiles (each tile is one
        # memcpy-able run), then a single dense GEMM over the union
        slab = jnp.take(rows3, tl, axis=0).reshape(e * tile_slots, kf)
        s1 = jnp.take(scales_t, tl, axis=0).reshape(e * tile_slots)
        sid1 = jnp.take(sids_t, tl, axis=0).reshape(e * tile_slots)
        sc = (
            jnp.dot(
                slab.astype(jnp.float32),
                qt,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            * s1[:, None]
        )  # [e*tile_slots, g] plane-1 ranking scores
        if cosine:
            nr = jnp.take(norms_t, tl, axis=0).reshape(e * tile_slots)
            sc = sc / jnp.maximum(nr[:, None] * qn[None, :, 0], 1e-12)
        sc = jnp.where(sid1[:, None] >= 0, sc, -jnp.inf)
        cms = jnp.max(sc.reshape(e, tile, chunk, g), axis=2)  # [E, tile, g]
        allc = jnp.moveaxis(cms, 2, 0).reshape(g, -1)  # [g, E*tile]
        cv, cpos = jax.lax.top_k(allc, min(kc, allc.shape[1]))
        tchunk = tl[cpos // tile] * tile + cpos % tile  # global chunk ids
        # starved selections (-inf chunk max) land on the guard chunk so
        # the gather below cannot touch an unprobed cell's items
        tchunk = jnp.where(jnp.isfinite(cv), tchunk, guard_chunk)
        iid = (
            tchunk[:, :, None] * chunk
            + jnp.arange(chunk, dtype=jnp.int32)[None, None, :]
        ).reshape(g, -1)
        sid = slot_ids[iid]
        sc = pt._gathered_pair_scores(
            mat_t, resid, scales, resid_scales, norms, q, qn, iid, cosine=cosine
        )
        sc = jnp.where(sid >= 0, sc, -jnp.inf)
        # pending overlay: exact f32 scan of the updated rows
        osc = jnp.dot(
            q,
            ov_rows.T,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        if cosine:
            osc = osc / jnp.maximum(ov_norms[None, :] * qn, 1e-12)
        osc = jnp.where(ov_ids[None, :] >= 0, osc, -jnp.inf)
        allv = jnp.concatenate([sc, osc], axis=1)
        alli = jnp.concatenate(
            [sid, jnp.broadcast_to(ov_ids[None, :], osc.shape)], axis=1
        )
        ke = min(k, allv.shape[1])
        v, p = jax.lax.top_k(allv, ke)
        out_ids = jnp.take_along_axis(alli, p, axis=1)
        # starved windows (k > finite candidates) pad with id -1, not a
        # garbage gather target — callers skip negatives
        out_ids = jnp.where(jnp.isfinite(v), out_ids, -1)
        if ke < k:
            v = jnp.pad(v, ((0, 0), (0, k - ke)), constant_values=-jnp.inf)
            out_ids = jnp.pad(out_ids, ((0, 0), (0, k - ke)), constant_values=-1)
        return v, out_ids

    if q_gbf.shape[0] == 1:
        v, i = one((q_gbf[0], tiles_ge[0]))
        return v[None], i[None]
    return jax.lax.map(one, (q_gbf, tiles_ge))


# -- query: full-probe exact mode ---------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("k", "n_seg", "seg", "cosine", "chunk")
)
def _full_topk(
    mat_t,
    resid,
    scales,
    resid_scales,
    norms,
    slot_ids,
    chunk_start,
    chunk_count,
    ov_rows,
    ov_ids,
    ov_norms,
    queries_gbf,
    *,
    k,
    n_seg,
    seg,
    cosine,
    chunk,
):
    """nprobe == n_cells: every occupied chunk is a candidate and every
    candidate rescores through the shared two-plane epilogue — the
    ascending-item-id candidate order makes the stable top_k break score
    ties toward the lowest id, exactly like the exact scan. O(n) gather:
    this mode exists for the bit-for-bit contract (and tiny catalogs),
    not for speed — the probed path above is the serving path."""
    int_max = jnp.iinfo(jnp.int32).max
    n_cells = chunk_start.shape[0]
    q_chunks = n_seg * seg

    def one(q):
        g = q.shape[0]
        qn = jnp.linalg.norm(q, axis=1, keepdims=True) if cosine else None
        lens = jnp.broadcast_to(chunk_count[None, :], (g, n_cells))
        cum = jnp.cumsum(lens, axis=1)
        j = jnp.broadcast_to(
            jnp.arange(q_chunks, dtype=jnp.int32)[None, :], (g, q_chunks)
        )
        # which cell does global candidate-chunk j fall into
        pos = jax.vmap(lambda c, jj: jnp.searchsorted(c, jj, side="right"))(cum, j)
        valid = pos < n_cells
        posc = jnp.minimum(pos, n_cells - 1)
        prev = cum - lens
        within = j - jnp.take_along_axis(prev, posc, axis=1)
        chk = jnp.where(valid, chunk_start[posc] + within, 0)
        iid = (
            chk[:, :, None] * chunk
            + jnp.arange(chunk, dtype=jnp.int32)[None, None, :]
        ).reshape(g, q_chunks * chunk)
        sid = slot_ids[iid]  # [g, m] original ids; -1 = padding/tombstone
        ok = jnp.repeat(valid, chunk, axis=1) & (sid >= 0)
        # ascending item id, padding last — the stable per-segment + final
        # top_k then tie-breaks toward the lowest item id
        key = jnp.where(ok, sid, int_max)
        ordr = jnp.argsort(key, axis=1)
        iid = jnp.take_along_axis(iid, ordr, axis=1)
        sid = jnp.take_along_axis(sid, ordr, axis=1)
        ok = jnp.take_along_axis(ok, ordr, axis=1)
        seg_items = seg * chunk
        kk = max(1, min(k, seg_items))
        iid_s = jnp.moveaxis(iid.reshape(g, n_seg, seg_items), 1, 0)
        sid_s = jnp.moveaxis(sid.reshape(g, n_seg, seg_items), 1, 0)
        ok_s = jnp.moveaxis(ok.reshape(g, n_seg, seg_items), 1, 0)

        def seg_step(carry, xs):
            ii, ss, oo = xs
            sc = pt._gathered_pair_scores(
                mat_t, resid, scales, resid_scales, norms, q, qn, ii,
                cosine=cosine,
            )
            sc = jnp.where(oo, sc, -jnp.inf)
            v, p = jax.lax.top_k(sc, kk)
            return carry, (v, jnp.take_along_axis(ss, p, axis=1))

        if n_seg == 1:
            _, (vs, ids) = seg_step(0, (iid_s[0], sid_s[0], ok_s[0]))
            allv, alli = vs, ids
        else:
            _, (vs, ids) = jax.lax.scan(seg_step, 0, (iid_s, sid_s, ok_s))
            allv = jnp.moveaxis(vs, 0, 1).reshape(g, n_seg * kk)
            alli = jnp.moveaxis(ids, 0, 1).reshape(g, n_seg * kk)
        # pending overlay: exact f32 scan of the updated rows
        osc = jnp.dot(
            q,
            ov_rows.T,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        if cosine:
            osc = osc / jnp.maximum(ov_norms[None, :] * qn, 1e-12)
        osc = jnp.where(ov_ids[None, :] >= 0, osc, -jnp.inf)
        allv = jnp.concatenate([allv, osc], axis=1)
        alli = jnp.concatenate(
            [alli, jnp.broadcast_to(ov_ids[None, :], osc.shape)], axis=1
        )
        ke = min(k, allv.shape[1])
        v, p = jax.lax.top_k(allv, ke)
        out_ids = jnp.take_along_axis(alli, p, axis=1)
        out_ids = jnp.where(jnp.isfinite(v), out_ids, -1)
        if ke < k:
            v = jnp.pad(v, ((0, 0), (0, k - ke)), constant_values=-jnp.inf)
            out_ids = jnp.pad(out_ids, ((0, 0), (0, k - ke)), constant_values=-1)
        return v, out_ids

    if queries_gbf.shape[0] == 1:
        v, i = one(queries_gbf[0])
        return v[None], i[None]
    return jax.lax.map(one, queries_gbf)


# -- query: entry points ------------------------------------------------------

# per-segment items for the full-probe gather (bounds the [kf, g, seg]
# f32 candidate planes to a few MB regardless of catalog size)
_SEG_ITEMS = 8192


def _group_queries(index: IVFIndex, queries: np.ndarray, order=None):
    """[n, feat] -> ([G, g, kf_pad] device f32, n, g). ``order`` permutes
    the queries before grouping (probe-locality sort)."""
    q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
    n = q.shape[0]
    if order is not None:
        q = q[order]
    kf_pad = index.mat_t.shape[0]
    g = max(1, min(QUERY_BLOCK, n))
    groups = -(-n // g)
    padded = np.zeros((groups * g, kf_pad), np.float32)
    padded[:n, : q.shape[1]] = q
    return jnp.asarray(padded.reshape(groups, g, kf_pad)), n, g


def top_k_device(
    index: IVFIndex,
    queries: np.ndarray,
    k: int,
    *,
    nprobe: int | None = None,
    cosine: bool = False,
):
    """(vals [n, k], ids [n, k]) device arrays; ids are ORIGINAL item
    row indices (-1 pads starved windows)."""
    np_ = index.resolve_nprobe(nprobe)
    kk = max(1, int(k))
    # an empty overlay shrinks to one masked dummy row: the overlay GEMM
    # against the full capacity (default 4096 rows) would otherwise cost
    # more than the probed scan itself
    if index.ov_used == 0:
        ov_rows, ov_ids, ov_norms = (
            index.ov_rows[:1],
            index.ov_ids[:1],
            index.ov_norms[:1],
        )
    else:
        ov_rows, ov_ids, ov_norms = index.ov_rows, index.ov_ids, index.ov_norms
    if np_ >= index.n_cells:
        q_gbf, n, g = _group_queries(index, queries)
        total_chunks = max(1, int(index.chunk_count_host.sum()))
        seg = max(1, _SEG_ITEMS // index.chunk)
        n_seg = -(-total_chunks // seg)
        if n_seg == 1:
            seg = total_chunks
        vals, ids = _full_topk(
            index.mat_t,
            index.resid,
            index.scales,
            index.resid_scales,
            index.norms,
            index.slot_ids,
            index.chunk_start,
            index.chunk_count,
            ov_rows,
            ov_ids,
            ov_norms,
            q_gbf,
            k=kk,
            n_seg=n_seg,
            seg=seg,
            cosine=cosine,
            chunk=index.chunk,
        )
        out_k = vals.shape[-1]
        return vals.reshape(-1, out_k)[:n], ids.reshape(-1, out_k)[:n]
    q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
    n = q.shape[0]
    kf_pad = index.mat_t.shape[0]
    qpad = np.zeros((n, kf_pad), np.float32)
    qpad[:, : q.shape[1]] = q
    cells = np.asarray(
        _route_cells(
            index.centroids_t,
            index.centroid_norms,
            index.chunk_count,
            jnp.asarray(qpad),
            nprobe=np_,
            cosine=cosine,
        )
    )
    if index.host_plane is not None or index.tier is not None:
        if index.tier is not None:
            # issue the async disk->RAM copies for every probed cell
            # before the group loop scans them in sequence
            index.tier.prefetch_cells(np.unique(cells))
        vals_np, ids_np = _host_topk(index, qpad, cells, kk, cosine)
        return jnp.asarray(vals_np), jnp.asarray(ids_np)
    # probe-locality sort: queries sharing a best cell land in the same
    # scan group, shrinking each group's cell union (the scan covers the
    # union, so overlap is pure savings); results unsort at the end
    order = np.argsort(cells[:, 0], kind="stable")
    g = max(1, min(QUERY_BLOCK, n))
    groups = -(-n // g)
    lists = _group_tile_lists(index, cells[order], g)
    qs = np.zeros((groups * g, kf_pad), np.float32)
    qs[:n] = qpad[order]
    qs = qs.reshape(groups, g, kf_pad)
    # bucket groups by pow2(union size): each bucket pads only to ITS
    # widest member, so one pathological union doesn't tax every group
    buckets: dict[int, list[int]] = {}
    for gi, t in enumerate(lists):
        buckets.setdefault(_pow2_ceil(max(1, len(t))), []).append(gi)
    row_src = []  # sorted-query row ranges, in bucket emission order
    parts_v, parts_i = [], []
    for e, gis in sorted(buckets.items()):
        tiles = _pack_tiles(index, [lists[gi] for gi in gis], e)
        v, i = _probe_topk(
            index.mat_rows,
            index.mat_t,
            index.resid,
            index.scales,
            index.resid_scales,
            index.norms,
            index.slot_ids,
            ov_rows,
            ov_ids,
            ov_norms,
            jnp.asarray(qs[gis]),
            tiles,
            k=kk,
            kc=pt._chunk_k(kk, e * index.tile_chunks),
            tile=index.tile_chunks,
            chunk=index.chunk,
            cosine=cosine,
        )
        parts_v.append(v.reshape(-1, v.shape[-1]))
        parts_i.append(i.reshape(-1, i.shape[-1]))
        for gi in gis:
            row_src.append(np.arange(gi * g, (gi + 1) * g, dtype=np.int64))
    stacked_v = parts_v[0] if len(parts_v) == 1 else jnp.concatenate(parts_v)
    stacked_i = parts_i[0] if len(parts_i) == 1 else jnp.concatenate(parts_i)
    # stacked row j holds sorted-query row_src[j]; compose with the
    # locality unsort so one device gather restores caller order
    where = np.empty(groups * g, np.int64)
    where[np.concatenate(row_src)] = np.arange(groups * g)
    inv = np.argsort(order)
    sel = jnp.asarray(where[inv].astype(np.int32))
    return stacked_v[sel], stacked_i[sel]


def top_k(
    index: IVFIndex,
    queries: np.ndarray,
    k: int,
    *,
    nprobe: int | None = None,
    cosine: bool = False,
):
    """Blocking host-side form: (ids [n, k] int32, vals [n, k] f32)."""
    vals, ids = top_k_device(index, queries, k, nprobe=nprobe, cosine=cosine)
    return np.asarray(ids), np.asarray(vals)


def top_k_device_indexed(
    index: IVFIndex,
    x_dev: jax.Array,
    indices: np.ndarray,
    k: int,
    *,
    nprobe: int | None = None,
    cosine: bool = False,
):
    """Index-submit twin: queries are rows of the device-resident X."""
    idx = np.atleast_1d(np.asarray(indices, dtype=np.int32))
    q = np.asarray(x_dev[jnp.asarray(idx)])  # device gather, tiny download
    return top_k_device(index, q, k, nprobe=nprobe, cosine=cosine)


# -- update path (speed-layer fold-ins) ---------------------------------------


@jax.jit
def _apply_overlay(slot_ids, ov_rows, ov_ids, ov_norms, dead, pos, rows, ids, nrm):
    # dead slots repeat their last entry when bucketed — set(-1) is
    # idempotent, so duplicates are harmless
    slot_ids = slot_ids.at[dead].set(-1)
    ov_rows = ov_rows.at[pos].set(rows)
    ov_ids = ov_ids.at[pos].set(ids)
    ov_norms = ov_norms.at[pos].set(nrm)
    return slot_ids, ov_rows, ov_ids, ov_norms


def update_rows(
    index: IVFIndex,
    rows: np.ndarray,
    values: np.ndarray,
    n_items: int | None = None,
) -> IVFIndex:
    """Fold updated item rows into the index via the pending overlay.

    Each touched row's cell slot is tombstoned (slot id -> -1) and its
    fresh vector lands in the overlay, which queries scan exactly — so a
    speed-layer fold-in is visible on the very next request regardless of
    which cells it routes to. Overlay rows store the two-plane
    DEQUANTIZED values (q1*s1 + q2*s2), so their scores match what a full
    rebuild would serve to f32 rounding.

    A full overlay DEGRADES instead of raising: the oldest overlay
    entries are evicted to ``index.pending_spill`` (raw values + fold-in
    time) and their slots reused, so the fold-in path stays O(batch)
    regardless of pressure — the spilled rows go invisible until
    ``compact_ivf`` folds them back. Re-updating an overlaid item
    refreshes its recency (and its spill entry, if any, is superseded).
    """
    rows = np.asarray(rows, dtype=np.int64)
    values = np.ascontiguousarray(np.atleast_2d(values), dtype=np.float32)
    if len(rows) == 0:
        return index
    count = int(index.n_items if n_items is None else n_items)
    # last write wins for duplicate ids in one batch
    last = {}
    for i, r in enumerate(rows):
        last[int(r)] = i
    ids = np.fromiter(last.keys(), dtype=np.int64, count=len(last))
    vals = values[np.fromiter(last.values(), dtype=np.int64, count=len(last))]

    cap = index.ov_rows.shape[0]
    ov_map = index.ov_map
    spill = index.pending_spill if index.pending_spill is not None else {}
    born = index.ov_born if index.ov_born is not None else {}
    now = time.time()
    used = index.ov_used
    pos = np.empty(len(ids), np.int32)
    fresh = 0
    for i, item in enumerate(ids):
        item = int(item)
        spill.pop(item, None)  # a fresh value supersedes any spilled one
        if item in ov_map:
            # keep the slot but refresh recency (dict order = age order)
            pos[i] = ov_map.pop(item)
            ov_map[item] = int(pos[i])
        elif used + fresh >= cap:
            if ov_map:
                # overlay full: evict the OLDEST entry to the spill queue
                # and reuse its slot (the scatter below overwrites it)
                old_id, old_slot = next(iter(ov_map.items()))
                ov_map.pop(old_id)
                if index.ov_raw_host is not None:
                    spill[old_id] = (
                        index.ov_raw_host[old_slot].copy(),
                        born.pop(old_id, now),
                    )
                else:
                    born.pop(old_id, None)
                pos[i] = old_slot
                ov_map[item] = int(old_slot)
            else:
                # every slot already belongs to THIS batch's fresh
                # entries (they join ov_map only after the scatter): the
                # incoming row spills directly — its raw value is right
                # here in vals, no slot round-trip needed
                if index.ov_raw_host is not None:
                    raw = np.zeros(index.mat_t.shape[0], np.float32)
                    raw[: vals.shape[1]] = vals[i]
                    spill[item] = (raw, now)
                born.pop(item, None)
                pos[i] = -1
        else:
            pos[i] = used + fresh
            fresh += 1
    dead = np.array(
        [
            index.id_to_slot[item]
            for item in ids
            if item < len(index.id_to_slot) and index.id_to_slot[item] >= 0
        ],
        dtype=np.int32,
    )

    keep = pos >= 0
    if not keep.all():
        # direct-spilled rows skip the overlay scatter, but any base-slot
        # versions of them still die (dead above covers them) and the
        # host mirror forgets the base mapping so lookups go to the spill
        for i in np.flatnonzero(~keep):
            item = int(ids[i])
            if item < len(index.id_to_slot):
                index.id_to_slot[item] = -1
        ids, vals, pos = ids[keep], vals[keep], pos[keep]
        if len(ids) == 0:
            if len(dead) and index.slot_ids_host is not None:
                index.slot_ids_host[dead] = -1
            slot_ids = index.slot_ids
            if len(dead):
                slot_ids = slot_ids.at[jnp.asarray(dead)].set(-1)
            return dataclasses.replace(
                index,
                slot_ids=slot_ids,
                n_items=max(count, index.n_items),
                ov_used=used + fresh,
            )

    q, s = pt._quantize_rows(vals)
    q2, s2 = pt._quantize_residual(vals, q, s)
    deq = q.astype(np.float32) * s[:, None] + q2.astype(np.float32) * s2[:, None]
    kf_pad = index.mat_t.shape[0]
    deq_pad = np.zeros((len(ids), kf_pad), np.float32)
    deq_pad[:, : vals.shape[1]] = deq
    nrm = np.linalg.norm(vals, axis=1)

    # bucket the scatter shapes like topn.update_rows (pad repeats the
    # last entry; rewriting the same overlay slot with the same row is
    # a no-op) so jit retraces O(log n) shapes
    def bucket(arr):
        m = len(arr)
        b = _pow2_ceil(m)
        if b == m:
            return arr
        return np.concatenate([arr, np.repeat(arr[-1:], b - m, axis=0)], axis=0)

    slot_ids, ov_rows, ov_ids, ov_norms = (
        index.slot_ids,
        index.ov_rows,
        index.ov_ids,
        index.ov_norms,
    )
    if len(dead):
        slot_ids, ov_rows, ov_ids, ov_norms = _apply_overlay(
            slot_ids,
            ov_rows,
            ov_ids,
            ov_norms,
            jnp.asarray(bucket(dead)),
            jnp.asarray(bucket(pos)),
            jnp.asarray(bucket(deq_pad)),
            jnp.asarray(bucket(ids.astype(np.int32))),
            jnp.asarray(bucket(nrm.astype(np.float32))),
        )
    else:
        pos_b = jnp.asarray(bucket(pos))
        ov_rows = ov_rows.at[pos_b].set(jnp.asarray(bucket(deq_pad)))
        ov_ids = ov_ids.at[pos_b].set(jnp.asarray(bucket(ids.astype(np.int32))))
        ov_norms = ov_norms.at[pos_b].set(jnp.asarray(bucket(nrm.astype(np.float32))))

    # host bookkeeping (see class docstring: serialized by the caller)
    if index.slot_ids_host is not None:
        if len(dead):
            index.slot_ids_host[dead] = -1  # tombstone in the host mirror
        index.ov_rows_host[pos] = deq_pad
        index.ov_ids_host[pos] = ids.astype(np.int32)
        index.ov_norms_host[pos] = nrm.astype(np.float32)
    if index.ov_raw_host is not None:
        raw_pad = np.zeros((len(ids), kf_pad), np.float32)
        raw_pad[:, : vals.shape[1]] = vals
        index.ov_raw_host[pos] = raw_pad
    for i, item in enumerate(ids):
        item = int(item)
        ov_map[item] = int(pos[i])
        born[item] = now
        if item < len(index.id_to_slot):
            index.id_to_slot[item] = -1
    return dataclasses.replace(
        index,
        slot_ids=slot_ids,
        ov_rows=ov_rows,
        ov_ids=ov_ids,
        ov_norms=ov_norms,
        n_items=max(count, index.n_items),
        ov_used=used + fresh,
    )


def capacity(index: IVFIndex) -> int:
    """Rows the handle can represent without a rebuild: the built catalog
    plus whatever overlay slots remain for appended items. (With a
    maintainer attached callers may exceed this — the overlay spills and
    compaction absorbs the growth — but absent one this is the honest
    always-visible bound.)"""
    return index.n_items + (index.ov_rows.shape[0] - index.ov_used)


# -- maintenance (background compaction; serving/maintain.py drives) ----------


@dataclasses.dataclass
class PendingSnapshot:
    """A consistent copy of everything compaction folds in: the overlay's
    raw rows plus the spill queue, with per-item fold-in times."""

    ids: np.ndarray  # [m] int64 item ids
    raw: np.ndarray  # [m, kf_pad] f32 RAW (pre-quantization) values
    born: dict  # item id -> fold-in wall-clock seconds
    taken_at: float  # wall-clock seconds at snapshot


def snapshot_pending(index: IVFIndex) -> PendingSnapshot:
    """Copy the overlay + spill queue out of the index.

    Call under the OWNER's serialization (the serving model's cache
    lock): ``compact_ivf`` then runs entirely on immutable device arrays
    plus these copies, so concurrent fold-ins mutating the live host
    bookkeeping (``ov_map``/``ov_raw_host``/``pending_spill``) never race
    the background compaction."""
    ids: list[int] = []
    rows: list[np.ndarray] = []
    born: dict[int, float] = {}
    src_born = index.ov_born or {}
    now = time.time()
    for item, slot in index.ov_map.items():
        ids.append(item)
        rows.append(index.ov_raw_host[slot].copy())
        born[item] = src_born.get(item, now)
    for item, (raw, b) in (index.pending_spill or {}).items():
        ids.append(item)
        rows.append(np.asarray(raw, dtype=np.float32))
        born[item] = float(b)
    kf_pad = index.mat_t.shape[0]
    raw = (
        np.vstack(rows).astype(np.float32, copy=False)
        if rows
        else np.zeros((0, kf_pad), np.float32)
    )
    return PendingSnapshot(np.asarray(ids, np.int64), raw, born, now)


def needs_maintenance(index, watermark: float = 0.5) -> bool:
    """Is it time to compact? True once anything spilled (those rows are
    invisible until compaction) or the overlay passed the watermark."""
    if not isinstance(index, IVFIndex):
        return False
    if index.pending_spill:
        return True
    cap = index.ov_rows.shape[0]
    return index.ov_used >= max(1, int(float(watermark) * cap))


def compact_ivf(
    index: IVFIndex,
    pending: PendingSnapshot | None = None,
    *,
    seed: int = 0,
    split_max_items: int = 0,
    merge_min_items: int = 0,
) -> tuple[IVFIndex, dict]:
    """Fold the overlay + spill queue into a fresh cell-contiguous layout
    WITHOUT retraining the coarse quantizer (the no-stop-the-world
    rebuild: SPFresh's LIRE rebalancing applied to the IVF tier).

    - retained rows keep their quantized codes/scales/norms VERBATIM —
      per-row quantization is deterministic, so the compacted planes are
      bit-identical to a from-scratch ``build_ivf`` over the same item
      set (the full-probe exactness contract transfers);
    - pending rows quantize fresh from their RAW values and assign to
      their nearest centroid;
    - tombstoned slots are garbage-collected by omission;
    - cells grown past ``split_max_items`` split via a local 2-means
      (children replace the parent centroid); cells starved below
      ``merge_min_items`` dissolve into their members' nearest surviving
      centroid. Zero thresholds auto-derive from the mean cell load
      (4x mean splits, mean/8 merges).

    Returns ``(new_index, stats)``; the new index starts with an empty
    overlay and spill queue. Runs on the caller's thread — the maintainer
    calls it OFF the request path and swaps the result in under the
    model's lock."""
    if pending is None:
        pending = snapshot_pending(index)
    feat = index.features
    chunk = index.chunk
    tile_chunks = index.tile_chunks
    ts = tile_chunks * chunk
    cells0 = index.n_cells

    # slot -> cell from the tile spans (cells are laid out contiguously
    # from slot 0 in cell order; the trailing guard tile maps to no cell)
    spans = (index.tile_count_host * ts).astype(np.int64)
    slot_cell = np.full(index.n_slots, -1, np.int64)
    slot_cell[: int(spans.sum())] = np.repeat(
        np.arange(cells0, dtype=np.int64), spans
    )

    sids = np.asarray(index.slot_ids)
    live = np.flatnonzero(sids >= 0)
    r_ids = sids[live].astype(np.int64)
    r_cell = slot_cell[live]
    r_q = np.asarray(index.mat_rows)[live][:, :feat]
    r_q2 = np.ascontiguousarray(np.asarray(index.resid)[:, live].T)[:, :feat]
    r_s = np.asarray(index.scales)[0, live]
    r_s2 = np.asarray(index.resid_scales)[0, live]
    r_n = np.asarray(index.norms)[0, live]

    centers = np.ascontiguousarray(np.asarray(index.centroids_t).T[:, :feat])
    p_ids = pending.ids
    if len(p_ids):
        p_raw = np.ascontiguousarray(pending.raw[:, :feat])
        p_cell = _assign_cells(p_raw, centers).astype(np.int64)
        p_q, p_s = pt._quantize_rows(p_raw)
        p_q2, p_s2 = pt._quantize_residual(p_raw, p_q, p_s)
        p_n = np.linalg.norm(p_raw, axis=1)
        ids = np.concatenate([r_ids, p_ids])
        cell = np.concatenate([r_cell, p_cell])
        q = np.vstack([r_q, p_q])
        q2 = np.vstack([r_q2, p_q2])
        s = np.concatenate([r_s, p_s])
        s2 = np.concatenate([r_s2, p_s2])
        nv = np.concatenate([r_n, p_n])
    else:
        ids, cell, q, q2, s, s2, nv = r_ids, r_cell, r_q, r_q2, r_s, r_s2, r_n
    n = len(ids)
    if n == 0:
        raise ValueError("compaction would produce an empty index")

    mean = max(1, n // max(1, cells0))
    merge_min = int(merge_min_items) or max(1, mean // 8)
    split_max = int(split_max_items) or max(mean * 4, merge_min + 1)

    # -- merges: starved cells dissolve into their nearest survivor ------
    counts = np.bincount(cell, minlength=cells0)
    victims = np.flatnonzero(counts < merge_min)
    merges = 0
    if len(victims) == cells0:  # keep the fattest cell alive
        victims = victims[victims != int(np.argmax(counts))]
    if len(victims):
        surv = np.setdiff1d(np.arange(cells0), victims, assume_unique=True)
        remap = np.full(cells0, -1, np.int64)
        remap[surv] = np.arange(len(surv))
        cell = remap[cell]
        mov = np.flatnonzero(cell < 0)
        if len(mov):
            deq = (
                q[mov].astype(np.float32) * s[mov, None]
                + q2[mov].astype(np.float32) * s2[mov, None]
            )
            cell[mov] = _assign_cells(deq, centers[surv]).astype(np.int64)
        centers = np.ascontiguousarray(centers[surv])
        merges = int(len(victims))

    # -- splits: overloaded cells split via a local 2-means --------------
    splits = 0
    counts = np.bincount(cell, minlength=len(centers))
    big = np.flatnonzero(counts > split_max)
    if len(big):
        from oryx_tpu.ops.kmeans import train_kmeans

        order_c = np.argsort(cell, kind="stable")
        bounds = np.zeros(len(centers) + 1, np.int64)
        np.cumsum(counts, out=bounds[1:])
        extra: list[np.ndarray] = []
        for c in big:
            mem = order_c[bounds[c] : bounds[c + 1]]
            deq = (
                q[mem].astype(np.float32) * s[mem, None]
                + q2[mem].astype(np.float32) * s2[mem, None]
            )
            minibatch = 32_768 if len(deq) > 65_536 else None
            sub_c, _cnt, _cost = train_kmeans(
                deq,
                2,
                iterations=4,
                init="k-means||",
                seed=seed + 17 * int(c),
                minibatch_size=minibatch,
            )
            sub_c = np.asarray(sub_c, dtype=np.float32)
            half = _assign_cells(deq, sub_c)
            if half.min() == half.max():
                continue  # degenerate split (all rows one side): skip
            centers[c] = sub_c[0]
            cell[mem[half == 1]] = len(centers) + len(extra)
            extra.append(sub_c[1])
            splits += 1
        if extra:
            centers = np.vstack([centers] + [e[None, :] for e in extra])

    n_items = max(index.n_items, int(ids.max()) + 1)
    new_index = _assemble_layout(
        ids,
        cell,
        centers,
        q,
        q2,
        s,
        s2,
        nv,
        feat=feat,
        chunk=chunk,
        tile_chunks=tile_chunks,
        cap=index.ov_rows.shape[0],
        n_items=n_items,
        host1=index.slot_ids_host is not None,
    )
    stats = {
        "folded": int(len(p_ids)),
        "live": int(len(r_ids)),
        "cells": int(len(centers)),
        "splits": int(splits),
        "merges": merges,
        "born": dict(pending.born),
        "taken_at": pending.taken_at,
    }
    return new_index, stats


def _assemble_layout(
    ids: np.ndarray,
    cell: np.ndarray,
    centers: np.ndarray,
    q: np.ndarray,
    q2: np.ndarray,
    s: np.ndarray,
    s2: np.ndarray,
    norms_v: np.ndarray,
    *,
    feat: int,
    chunk: int,
    tile_chunks: int,
    cap: int,
    n_items: int,
    host1: bool,
) -> IVFIndex:
    """Lay (ids, cell assignment, codes) out cell-contiguous and
    tile-aligned — ``build_ivf``'s layout stage over PRE-QUANTIZED rows.
    Within a cell items order by ascending id, preserving the exact
    path's tie direction."""
    n = len(ids)
    cells = len(centers)
    tile_slots = tile_chunks * chunk
    order = np.lexsort((ids, cell))  # cell-major, ascending id within
    counts = np.bincount(cell, minlength=cells).astype(np.int64)
    chunk_counts = -(-counts // chunk)
    tile_counts = -(-chunk_counts // tile_chunks)
    spans = tile_counts * tile_slots
    item_starts = np.zeros(cells + 1, np.int64)
    np.cumsum(counts, out=item_starts[1:])
    slot_base = np.zeros(cells + 1, np.int64)
    np.cumsum(spans, out=slot_base[1:])
    n_slots = int(slot_base[-1]) + tile_slots  # +1 guard tile
    pos_in_cell = np.arange(n, dtype=np.int64) - np.repeat(
        item_starts[:-1], counts
    )
    slots_sorted = np.repeat(slot_base[:-1], counts) + pos_in_cell

    kf_pad = pt._ceil_to(feat, pt._INT8_FEAT_MULTIPLE)
    mat_t = np.zeros((kf_pad, n_slots), np.int8)
    resid = np.zeros((kf_pad, n_slots), np.int8)
    mat_rows = np.zeros((n_slots, kf_pad), np.int8)
    scales = np.ones((1, n_slots), np.float32)
    rscales = np.ones((1, n_slots), np.float32)
    norms = np.zeros((1, n_slots), np.float32)
    slot_ids = np.full(n_slots, -1, np.int32)
    slot_ids[slots_sorted] = ids[order].astype(np.int32)
    id_to_slot = np.full(n_items, -1, np.int32)
    id_to_slot[ids[order]] = slots_sorted.astype(np.int32)
    host_plane = np.zeros((n_slots, kf_pad), np.float32) if host1 else None
    slice_rows = 1_000_000  # bounds the host transient like build_ivf
    for beg in range(0, n, slice_rows):
        rows = order[beg : beg + slice_rows]
        sl = slots_sorted[beg : beg + slice_rows]
        qs_ = q[rows][:, :feat]
        q2s_ = q2[rows][:, :feat]
        ss_ = s[rows]
        s2s_ = s2[rows]
        mat_t[:feat, sl] = qs_.T
        resid[:feat, sl] = q2s_.T
        mat_rows[sl, :feat] = qs_
        scales[0, sl] = ss_
        rscales[0, sl] = s2s_
        norms[0, sl] = norms_v[rows]
        if host_plane is not None:
            host_plane[sl, :feat] = (
                qs_.astype(np.float32) * ss_[:, None]
                + q2s_.astype(np.float32) * s2s_[:, None]
            )

    cent_t = np.zeros((kf_pad, cells), np.float32)
    cent_t[:feat] = centers.T

    return IVFIndex(
        mat_t=jnp.asarray(mat_t),
        resid=jnp.asarray(resid),
        mat_rows=jnp.asarray(mat_rows),
        scales=jnp.asarray(scales),
        resid_scales=jnp.asarray(rscales),
        norms=jnp.asarray(norms),
        slot_ids=jnp.asarray(slot_ids),
        centroids_t=jnp.asarray(cent_t),
        centroid_norms=jnp.asarray(np.linalg.norm(centers, axis=1)),
        chunk_start=jnp.asarray((slot_base[:-1] // chunk).astype(np.int32)),
        chunk_count=jnp.asarray(chunk_counts.astype(np.int32)),
        ov_rows=jnp.zeros((cap, kf_pad), jnp.float32),
        ov_ids=jnp.full((cap,), -1, jnp.int32),
        ov_norms=jnp.zeros((cap,), jnp.float32),
        n_items=n_items,
        features=feat,
        chunk=chunk,
        tile_chunks=tile_chunks,
        chunk_count_host=chunk_counts,
        tile_start_host=slot_base[:-1] // tile_slots,
        tile_count_host=tile_counts,
        id_to_slot=id_to_slot,
        ov_map={},
        ov_used=0,
        host_plane=host_plane,
        slot_ids_host=slot_ids.copy() if host1 else None,
        norms_host=norms[0].copy() if host1 else None,
        ov_rows_host=np.zeros((cap, kf_pad), np.float32) if host1 else None,
        ov_ids_host=np.full((cap,), -1, np.int32) if host1 else None,
        ov_norms_host=np.zeros((cap,), np.float32) if host1 else None,
        ov_raw_host=np.zeros((cap, kf_pad), np.float32),
        ov_born={},
        pending_spill={},
    )


# -- tiered host plane (native/store.py) --------------------------------------


def attach_tiered_plane(index: IVFIndex, plane=None) -> IVFIndex:
    """Move the host stage-1 plane into the tiered HBM->RAM->disk cell
    store. Returns a new handle with ``tier`` set and the flat
    ``host_plane`` dropped (the hot tier's working set replaces it);
    a no-op when tiering is off or the index has no host plane. Pass a
    prebuilt ``plane`` to adopt one (tests)."""
    if index.host_plane is None or index.tier is not None:
        return index
    if plane is None:
        from oryx_tpu.native import store as fstore

        if not fstore.tier_active():
            return index
        plane = fstore.TieredHostPlane.build(
            index.host_plane,
            tile_start=np.asarray(index.tile_start_host, np.int64),
            tile_count=np.asarray(index.tile_count_host, np.int64),
            tile_slots=index.tile_chunks * index.chunk,
            centroids=np.ascontiguousarray(np.asarray(index.centroids_t)),
            centroid_norms=np.asarray(index.centroid_norms),
        )
    return dataclasses.replace(index, tier=plane, host_plane=None)
