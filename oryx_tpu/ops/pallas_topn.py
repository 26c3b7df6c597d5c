"""Pallas TPU kernel: fused item-scoring + top-k for ALS serving.

The serving hot loop is "score every item against a user vector, keep the
best k" (reference: ALSServingModel.topN / TopNConsumer.java scanning LSH
partitions on a thread pool, VectorMath.dot per item). On TPU the exact
scan is one matmul — but the naive XLA program (``scores = Q @ Y.T`` then
``lax.top_k``) writes the full [b, n_items] score matrix to HBM and reads
it back for the top-k, which at 1M+ items costs more bandwidth than
reading the item matrix itself. This module fuses the two:

- the item matrix is laid out feature-major ``[k_feat, n_items]`` so each
  grid step streams a contiguous ``[k_feat, BLOCK_N]`` block of items
  through VMEM (Mosaic double-buffers blocks across the grid);
- each step computes ``[b, BLOCK_N]`` scores on the MXU with float32
  accumulation (items may be stored bfloat16 or row-quantized int8,
  halving / quartering HBM traffic);
- ONE kernel (``_topn_kernel``, ``oryx_topn_scan`` on the device's
  timeline) keeps one sorted running top-k in VMEM scratch: its k-th
  best is a threshold, a score tile in which no row beats its own is
  skipped, and in a tile that is not, only the scores above the
  threshold are taken, each inserted straight into the sorted state
  (``_insert_beaten``: one round an entry, so the pass hardly depends on
  how many distinct rows a batch holds; TPU v5e, PR 25: 20M x 50 float32,
  k 32, 6.18 ms for 8 copies of one row and 6.38 ms for 16 distinct rows,
  against 5.5 ms to stream the 4.48 GB as stored; 5M x 250: 6.93-6.95 ms
  against 6.25 ms). The state is ``ceil(k / 128)`` vregs a row, whatever
  k is asked;
- a scan is always ``[K, b]`` GROUPS of at most ``MAX_GROUP_ROWS`` query
  rows, run one after another under ``lax.map`` inside one jitted
  program (one group is K = 1): the ``[b, SCORE_TILE]`` score tile of a
  larger group does not fit VMEM, and more rows are more groups;
- only the k best ever reach HBM — the full score matrix never does.

HBM traffic per group drops from ``n*k_feat*4 + 2*b*n*4`` bytes to
``n*k_feat*{1|2|4}`` — a 2-12x win for the bandwidth-bound scan.

int8 handles store one f32 dequantization scale per item row
(``absmax/127``); scores dequantize by a single post-dot multiply, and
cosine scoring folds the cached item norms into that same multiplier so
the kernel never rescales twice.

On non-TPU backends the one public entry (``scan_groups``) runs an XLA
twin of the same blocked scan (``lax.scan`` over feature-major item
blocks, block-local ``lax.top_k``, final candidate merge) instead of
materializing [b, n] scores; ``interpret=True`` forces the Pallas kernel
under the interpreter (used by the CPU parity tests).
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Score-tile width. [b=256, 4096] f32 scores + the iota/mask temps fit
# the 16 MB scoped-VMEM limit of a v5e; 8192 does not (measured 20.7 MB).
SCORE_TILE = 4096
# Sub-tiles streamed per grid step: the item block per step is
# [k_feat, SCORE_TILE * SUBTILES] (bf16, ~1.6 MB at 4) while the
# score/iota tiles stay SCORE_TILE wide: every grid step has a fixed
# cost, so fewer, fatter steps (not re-measured on the v5e since the
# kernels were brought up there, PR 21). 8 exceeds the 16 MB
# scoped-VMEM limit at b=256.
SUBTILES = 4
BLOCK_N = SCORE_TILE * SUBTILES  # items consumed per grid step

# Query rows of one scan group: the kernel keeps the whole
# [b, SCORE_TILE] score tile resident, which stops fitting scoped VMEM
# past ~256 rows. More rows are more groups (``group_rows``).
MAX_GROUP_ROWS = 256

# Items per lax.scan step of the XLA (non-TPU) blocked scan. Rounded down
# to a BLOCK_N multiple that divides the padded item count. 16K keeps the
# [b, block] score tile inside L2/L3 so the block-local top-k reads cache,
# not DRAM (measured best of 4K..128K on a one-core CPU host).
XLA_SCAN_BLOCK = 16384

# Oversampling factor for quantized scans: the int8 plane ranks the scan,
# then the top (RESCORE_OVERSAMPLE * k) candidates, at most
# OVERSAMPLE_CAP, are re-scored against the residual plane (int8 codes of
# what the first plane dropped) before the final top-k.
RESCORE_OVERSAMPLE = 4
OVERSAMPLE_CAP = 128

# Chunk width of the quantized XLA scan's candidate selection: the scan
# reduces scores to per-chunk maxes (a reduce that fuses into the GEMM's
# epilogue — wide lax.top_k inside the scan body does not), the top-m
# chunks by max provably contain the top-m items, and only those chunks'
# columns are gathered and scored exactly afterwards.
_CHUNK = 32

# How many chunks that selection keeps: the top-k chunks by primary-plane
# max already provably contain the primary top-k items, and every kept
# chunk drags in its _CHUNK-1 neighbors, so a modest factor over k yields
# a ~30x item-level oversample for the exact two-plane rescore. The tail
# (gather + rescore) is linear in this count — keep it lean.
CHUNK_OVERSAMPLE = 1.25


def _chunk_k(k: int, chunks: int) -> int:
    return min(max(int(round(CHUNK_OVERSAMPLE * k)), k + 2), chunks)


# int8 operand tiles are (32 sublanes, 128 lanes): the feature dim of a
# quantized matrix pads to a 32 multiple (zero-filled; queries pad alike)
_INT8_FEAT_MULTIPLE = 32


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _is_int8(dtype) -> bool:
    if dtype is None:
        return False
    try:
        return np.dtype(dtype) == np.dtype(np.int8)
    except TypeError:  # pragma: no cover - exotic dtype objects
        return False


# A device array's second-minor dimension is tiled: 8 sublanes of 4-byte
# elements, 16 of 2-byte, 32 of 1-byte.
_SUBLANES = 8


def tail_rows(k_feat: int, dtype) -> int:
    """Rows of the tail plane a [n, k_feat] item matrix of `dtype` is
    stored with; 0 = one plane, as ever. THE rule of the split layout,
    decided by what the matrix is and nothing else:

    a float32 plane ``[k_feat, n]`` is stored in tiles of 8 sublanes, so
    50 features stream 56 rows an item (12 % of every pass zeros) and 250
    stream 256. A plane of 1, 2 or 4 rows is stored in tiles of its own
    height. So where ``t = k_feat % 8`` is 1-4 and there is a whole tile
    before it, the last t rows live in a plane of 1, 2 or 4 rows (3 rides
    as 4) and the main plane keeps whole tiles: stored rows = logical
    rows (one more where t = 3). With t = 5-7 a 4-row tail cannot hold
    them and the padding is 1-3 rows: the main plane keeps them, padded
    as ever. bfloat16 and int8 matrices keep one plane, exactly as they
    were: their tiles are 16 and 32 rows (int8 is padded explicitly,
    ``_INT8_FEAT_MULTIPLE``), how the chip stores a small plane of theirs
    was not measured, and no benchmark cell serves them (they are its
    control)."""
    t = k_feat % _SUBLANES
    if np.dtype(dtype) != np.float32 or k_feat < _SUBLANES or not 1 <= t <= 4:
        return 0
    return _plane_rows_stored(t, 4)


def _plane_rows_stored(rows: int, itemsize: int) -> int:
    """Rows the device stores for a [rows, n] plane: whole sublane tiles,
    or for a 4-byte plane of at most 4 rows a tile of its own height (1,
    2 or 4; read off the compiled programs' operand layouts, T(2,128))."""
    if itemsize == 4 and rows <= 4:
        return 1 << (rows - 1).bit_length()
    return _ceil_to(rows, _SUBLANES * 4 // itemsize)


def split_features(rows: np.ndarray, cols: int) -> tuple[np.ndarray, np.ndarray]:
    """[n, k] float32 rows whose ``tail_rows`` is not 0 -> (main plane,
    tail plane), both float32 and feature-major with `cols` columns."""
    k = rows.shape[1]
    k_main = k - k % _SUBLANES
    return (
        feature_major(rows[:, :k_main], cols, np.float32),
        feature_major(rows[:, k_main:], cols, np.float32, tail_rows(k, np.float32)),
    )


def stored_feature_rows(up) -> int:
    """Feature rows an item that the device stores for the scanned plane
    of a streaming or sharded handle: the main plane padded to its dtype's
    sublane tile, plus the tail's."""
    return _plane_rows_stored(up.mat_t.shape[0], up.mat_t.dtype.itemsize) + (
        0 if up.tail is None else up.tail.shape[0]
    )


def note_feature_rows(up) -> None:
    """The two gauges that say whether the split engaged, set at upload."""
    from oryx_tpu.common.metrics import registry as metrics

    logical = up.features if up.features is not None else up.mat_t.shape[0]
    metrics.gauge("serving.scan.feature-rows.logical").set(logical)
    metrics.gauge("serving.scan.feature-rows.stored").set(stored_feature_rows(up))


@dataclass(frozen=True)
class StreamingItemMatrix:
    """Device-resident item factors in the kernel's feature-major layout."""

    # [k_feat(_pad), n_padded]; f32, bf16, or row-quantized int8. With a
    # ``tail`` it is the MAIN plane: the first ``k_feat - k_feat % 8`` rows
    mat_t: jax.Array
    norms: jax.Array  # [1, n_padded] f32 (L2 norms of the ORIGINAL f32 rows)
    n_items: int
    # int8 handles only: per-item dequantization scale (absmax/127, f32,
    # 1.0 for all-zero rows so dequantizing is always a plain multiply)
    scales: jax.Array | None = None
    # true feature count where the stored rows are not it: int8 sublane
    # padding, or a tail plane (None = mat_t's own rows)
    features: int | None = None
    # int8 handles only: residual plane — int8 codes of (row - codes * s),
    # with its own per-row scale. Never scanned: only the top-(~4k)
    # candidates per query gather it for a ~14-bit-effective rescore, so
    # scan traffic stays 1 B/feature while recall matches f32.
    resid: jax.Array | None = None
    resid_scales: jax.Array | None = None
    # float32 handles whose feature count is 1-4 over a multiple of 8
    # (``tail_rows``): the last ``k_feat % 8`` feature rows, [1 | 2 | 4,
    # n_padded] float32, scored on the VPU beside the main plane's dot
    tail: jax.Array | None = None

    @property
    def num_features(self) -> int:
        return self.features if self.features is not None else self.mat_t.shape[0]

    @property
    def quantized(self) -> bool:
        return self.scales is not None


def _quantize_rows(mat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Row-wise symmetric int8: q = rint(row / s), s = absmax/127 (1.0 for
    all-zero rows). Same rule as the device-side requantize in
    ``topn.update_rows`` so a scatter round-trips bit-exactly."""
    absmax = np.max(np.abs(mat), axis=1)
    s = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(mat / s[:, None]), -127, 127).astype(np.int8)
    return q, s


def _quantize_residual(
    mat: np.ndarray, q: np.ndarray, s: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Second int8 plane: quantize what the first plane dropped
    (``row - q * s``, at most s/2 per element) with its own per-row
    absmax/127 scale — together the planes carry ~14 significant bits,
    enough for candidate rescoring to match f32 ranking."""
    r = mat - q.astype(np.float32) * s[:, None]
    am = np.max(np.abs(r), axis=1)
    s2 = np.where(am > 0, am / 127.0, 1.0).astype(np.float32)
    q2 = np.clip(np.rint(r / s2[:, None]), -127, 127).astype(np.int8)
    return q2, s2


# Laying a packed matrix out for the kernel is a transposing copy of every
# byte: done in bands of rows on a few threads (numpy's copy loops release
# the GIL), a band a task. On one thread it was most of a 5 GB shard's
# upload, and the norms made a temporary as large as the shard.
_LAYOUT_BAND = 1 << 16
_LAYOUT_THREADS = min(16, os.cpu_count() or 1)


def _in_bands(fn, n: int) -> None:
    """fn(lo, hi) for every band of rows [lo, hi) of n."""
    bands = [(lo, min(n, lo + _LAYOUT_BAND)) for lo in range(0, n, _LAYOUT_BAND)]
    if len(bands) <= 1:
        for band in bands:
            fn(*band)
        return
    with ThreadPoolExecutor(max_workers=_LAYOUT_THREADS) as pool:
        list(pool.map(lambda band: fn(*band), bands))


def feature_major(rows: np.ndarray, cols: int, dtype, height: int | None = None) -> np.ndarray:
    """[n, k] row-major -> the kernel's [height, cols] feature-major plane
    (``out[:k, :n] = rows.T``, zero elsewhere), cast to `dtype`."""
    n, k = rows.shape
    out = np.empty((height or k, cols), dtype=dtype)
    out[:, n:] = 0
    out[k:, :n] = 0

    def band(lo, hi):
        out[:k, lo:hi] = rows[lo:hi].T

    _in_bands(band, n)
    return out


def row_norms(rows: np.ndarray) -> np.ndarray:
    """[n] float32 L2 norms of the rows, bit for bit ``np.linalg.norm(rows,
    axis=1)`` (a row's sum does not depend on its neighbours)."""
    out = np.empty(rows.shape[0], dtype=np.float32)

    def band(lo, hi):
        out[lo:hi] = np.linalg.norm(rows[lo:hi], axis=1)

    _in_bands(band, rows.shape[0])
    return out


def upload_streaming(matrix: np.ndarray, dtype=jnp.float32) -> StreamingItemMatrix:
    """Pad items up to a BLOCK_N multiple and move [k, n] to device.

    ``dtype=jnp.int8`` row-quantizes: each item row stores int8 codes plus
    one f32 scale, cutting the scan's HBM traffic 4x vs f32 while keeping
    per-row dynamic range (a global scale would clip hot rows)."""
    n, k_feat = matrix.shape
    n_pad = max(BLOCK_N, _ceil_to(n, BLOCK_N))
    mat = np.asarray(matrix, dtype=np.float32)
    norms = np.zeros((1, n_pad), dtype=np.float32)
    norms[0, :n] = row_norms(mat)
    if _is_int8(dtype):
        q, s = _quantize_rows(mat)
        q2, s2 = _quantize_residual(mat, q, s)
        kf_pad = _ceil_to(k_feat, _INT8_FEAT_MULTIPLE)
        mat_t = feature_major(q, n_pad, np.int8, kf_pad)
        resid = feature_major(q2, n_pad, np.int8, kf_pad)
        scales = np.ones((1, n_pad), dtype=np.float32)
        scales[0, :n] = s
        rscales = np.ones((1, n_pad), dtype=np.float32)
        rscales[0, :n] = s2
        up = StreamingItemMatrix(
            mat_t=jnp.asarray(mat_t),
            norms=jnp.asarray(norms),
            n_items=n,
            scales=jnp.asarray(scales),
            features=k_feat if kf_pad != k_feat else None,
            resid=jnp.asarray(resid),
            resid_scales=jnp.asarray(rscales),
        )
    elif tail_rows(k_feat, dtype):
        main, tail = split_features(mat, n_pad)
        up = StreamingItemMatrix(
            mat_t=jnp.asarray(main), norms=jnp.asarray(norms), n_items=n,
            features=k_feat, tail=jnp.asarray(tail),
        )
    else:
        up = StreamingItemMatrix(
            mat_t=jnp.asarray(feature_major(mat, n_pad, np.float32), dtype=dtype),
            norms=jnp.asarray(norms),
            n_items=n,
        )
    note_feature_rows(up)
    return up


def _dot_precision_for(q, quantized: bool):
    # f32 items get true f32 accumulation (TPU default would silently drop
    # to bf16 passes); bf16 items are the intentional fast path. int8
    # items upcast in-register and take bf16 MXU passes: the quantization
    # step (~0.4% of row absmax) dominates the accumulation error, and
    # DEFAULT runs the MXU at 6x the f32-HIGHEST rate.
    if quantized or q.dtype != jnp.float32:
        return jax.lax.Precision.DEFAULT
    return jax.lax.Precision.HIGHEST


def _score_tile(q, mat_s, aux_s, qn, *, cosine, quantized, tail_s=None):
    """[b, tile] scores for one item sub-tile. ``aux_s`` is the item-norm
    tile (unquantized) or the folded dequant multiplier (quantized; cosine
    norms already divided in outside the kernel). ``tail_s`` is the tail
    plane's [t, tile] sub-tile where the matrix has one (float32 only):
    the query's columns past the main plane's rows are its, a broadcast
    multiply-add a row on the VPU, exact float32."""
    if quantized:
        mat_s = mat_s.astype(jnp.float32)
    k_main = mat_s.shape[0]
    scores = jnp.dot(
        q if tail_s is None else q[:, :k_main],
        mat_s,
        preferred_element_type=jnp.float32,
        precision=_dot_precision_for(q, quantized),
    )
    if tail_s is not None:
        for i in range(tail_s.shape[0]):  # unrolled: 1, 2 or 4 rows
            scores = scores + q[:, k_main + i : k_main + i + 1] * tail_s[i : i + 1, :]
    if quantized:
        scores = scores * aux_s
        if cosine:
            scores = scores / jnp.maximum(qn, 1e-12)
    elif cosine:
        scores = scores / jnp.maximum(aux_s * qn, 1e-12)
    return scores


# The kernel's running top-k is held in whole 128-lane vregs, so shifting
# a row is a native lane roll: one vreg a row up to k = 128, two for a k
# bucket of 256.
_STATE_LANES = 128


def _insert_beaten(sc, m, local_cols, base, vstate, istate, *, k, int_max, neg_inf, counts):
    """Fold a gated score tile into the sorted running top-k, one entry a
    round, for as many rounds as some row still holds a score strictly
    above its running k-th best (the caller's gate saw at least one).

    A round takes each row's largest remaining score ``m`` (ties: lowest
    column) and puts it where a stable sort would: behind every state
    entry ``>= m``, the entries below it moving one lane up. A row whose
    ``m`` does not beat its k-th best changes no lane under k, so rows
    with nothing to insert, padded rows among them, ride along. Entries
    and order are those of a stable global sort by (score desc, item id
    asc): equal scores of one tile come out lowest column first and land
    behind their equals, and a score equal to the k-th best stays out,
    the earlier item having the lower id. A tile costs at most k rounds:
    k entries from one tile lift the k-th best to the tile's own k-th.
    Lanes from k up hold what fell off the end (all <= the k-th best, so
    never counted as ``>= m``) and are never read or written out."""
    lane = jax.lax.broadcasted_iota(jnp.int32, vstate.shape, 1)

    def one_round(carry):
        sc, m, _ = carry
        at = jnp.min(jnp.where(sc == m, local_cols, int_max), axis=1, keepdims=True)
        v, i = vstate[...], istate[...]
        up_v = pltpu.roll(v, 1, 1)  # lane j holds lane j - 1
        stays = v >= m  # a prefix of the row: the state is sorted
        lands = (up_v >= m) | (lane == 0)  # first lane past that prefix
        vstate[...] = jnp.where(stays, v, jnp.where(lands, m, up_v))
        istate[...] = jnp.where(
            stays, i, jnp.where(lands, at + base, pltpu.roll(i, 1, 1))
        )
        if counts is not None:
            counts[0, 1] += 1
        sc = jnp.where(local_cols == at, neg_inf, sc)
        m = jnp.max(sc, axis=1, keepdims=True)
        return sc, m, jnp.any(m > vstate[:, k - 1 : k])

    jax.lax.while_loop(lambda carry: carry[2], one_round, (sc, m, True))


def _topn_kernel(
    q_ref, mat_ref, aux_ref, *rest,
    k, n_items, cosine, quantized, grid, subtiles, tailed=False
):
    """One grid step: score a [k_feat, BLOCK_N] item block and fold it
    into the running top-k carried in VMEM scratch across grid steps.

    The running k-th best is a threshold: a score tile in which no row
    beats its own is skipped, and in one that is not, selection runs one
    round for each entry made (``_insert_beaten``), not k rounds.
    ``rest`` is the two outputs and the two scratch refs, with a third,
    SMEM output between them where the call asked for one ([gated tiles,
    rounds] of the pass), and in front of them the optional inputs: the
    tail plane's [t, BLOCK_N] block where ``tailed``, then an SMEM input
    where ``n_items`` is None: the valid item count, known only on the
    device (a mesh shard's own row count, ops/topn.py)."""
    tail_ref = None
    if tailed:
        tail_ref, *rest = rest
    if n_items is None:
        n_ref, *rest = rest
        n_items = n_ref[0]
    vals_ref, idx_ref, *counts, vstate, istate = rest
    counts = counts[0] if counts else None
    block = pl.program_id(0)
    b = q_ref.shape[0]
    neg_inf = jnp.float32(-jnp.inf)
    int_max = jnp.int32(2**31 - 1)

    @pl.when(block == 0)
    def _():
        vstate[...] = jnp.full(vstate.shape, neg_inf, jnp.float32)
        istate[...] = jnp.zeros(istate.shape, jnp.int32)
        if counts is not None:
            counts[0, 0] = 0
            counts[0, 1] = 0

    q = q_ref[:]  # [b, k_feat]
    qn = None
    if cosine:
        qn = jnp.sqrt(
            jnp.sum(q.astype(jnp.float32) * q.astype(jnp.float32), axis=1, keepdims=True)
        )
    # local (per-tile) column ids: one [b, SCORE_TILE] iota reused by every
    # sub-tile keeps VMEM at two tiles regardless of how many sub-tiles a
    # grid step streams; the global item id is base + local.
    local_cols = jax.lax.broadcasted_iota(jnp.int32, (b, SCORE_TILE), 1)
    for s in range(subtiles):  # unrolled: static sub-tile slices
        base = block * (SCORE_TILE * subtiles) + s * SCORE_TILE
        scores = _score_tile(
            q,
            mat_ref[:, s * SCORE_TILE : (s + 1) * SCORE_TILE],
            aux_ref[:, s * SCORE_TILE : (s + 1) * SCORE_TILE],
            qn,
            cosine=cosine,
            quantized=quantized,
            tail_s=tail_ref[:, s * SCORE_TILE : (s + 1) * SCORE_TILE] if tailed else None,
        )
        scores = jnp.where(local_cols < n_items - base, scores, neg_inf)
        kth = vstate[:, k - 1 : k]  # worst of the running top-k, [b, 1]
        best = jnp.max(scores, axis=1, keepdims=True)
        need = jnp.any(best > kth)

        @pl.when(need)
        def _(scores=scores, best=best, base=base):
            if counts is not None:
                counts[0, 0] += 1
            _insert_beaten(
                scores, best, local_cols, base, vstate, istate,
                k=k, int_max=int_max, neg_inf=neg_inf, counts=counts,
            )

    @pl.when(block == grid - 1)
    def _():
        vals_ref[...] = vstate[:, :k]
        idx_ref[...] = istate[:, :k]


def _scan_k(k: int, n_items: int, resid) -> int:
    """Candidates the scan keeps per query before the residual rescore
    trims back to k."""
    if resid is None:
        return k
    m = min(max(RESCORE_OVERSAMPLE * k, 32), OVERSAMPLE_CAP, n_items)
    return max(m, k)


def _rescore_topk(vals, idxs, q, qn, resid, resid_scales, norms, *, k, cosine):
    """Trim oversampled int8 candidates to the final top-k by adding the
    residual plane's contribution: gather the candidates' residual codes
    (a few KB — never the whole plane), one tiny batched dot, re-rank.
    Candidates are re-sorted by item id first so the stable top_k keeps
    breaking ties toward the lowest index."""
    order = jnp.argsort(idxs, axis=1)
    ii = jnp.take_along_axis(idxs, order, axis=1)  # [b, m] ascending ids
    vv = jnp.take_along_axis(vals, order, axis=1)
    cand = jnp.take(resid, ii, axis=1).astype(jnp.float32)  # [kf, b, m]
    corr = jnp.einsum(
        "bf,fbm->bm", q, cand, precision=jax.lax.Precision.HIGHEST
    )
    aux2 = resid_scales[0]
    if cosine:
        aux2 = aux2 / jnp.maximum(norms[0], 1e-12)
    corr = corr * aux2[ii]
    if cosine:
        corr = corr / jnp.maximum(qn, 1e-12)
    # padding candidates carry -inf from the scan; keep them out
    sc = jnp.where(jnp.isfinite(vv), vv + corr, -jnp.inf)
    v, pos = jax.lax.top_k(sc, k)
    return v, jnp.take_along_axis(ii, pos, axis=1)


def pack_hits(vals, idxs, download_dtype=None):
    """What a scan program hands back for its float32 ``vals`` and int32
    ``idxs``, both [K, b, k]: ONE int32 array [K, b, 2k], the scores' bit
    patterns then the ids, so that a pass is one download (the same 8 B a
    hit; the bit-cast loses nothing). Where the scores travel narrower
    (``download_dtype``: the bf16 / int8 handles' 6 B a hit) the two
    arrays have no common width and stay a pair. ``split_hits`` undoes
    either."""
    if download_dtype is not None:
        return vals.astype(download_dtype), idxs
    return jnp.concatenate([jax.lax.bitcast_convert_type(vals, jnp.int32), idxs], axis=-1)


def split_hits(hits):
    """(scores [..., k], ids [..., k]) of what a scan program handed back
    (``pack_hits``), on the device or, for a NumPy array, as views."""
    if isinstance(hits, tuple):
        return hits
    k = hits.shape[-1] // 2
    if isinstance(hits, np.ndarray):
        return hits[..., :k].view(np.float32), hits[..., k:]
    return jax.lax.bitcast_convert_type(hits[..., :k], jnp.float32), hits[..., k:]


@functools.partial(
    jax.jit, static_argnames=("k", "n_items", "cosine", "interpret", "download_dtype")
)
def _streaming_topk_multi(
    mat_t, norms, scales, resid, resid_scales, queries_kb, *,
    k, n_items, cosine, interpret, download_dtype=None, tail=None,
):
    """K full-matrix scans in ONE dispatch: lax.map runs the pallas scan
    sequentially over [K, b, feat] query groups inside a single jitted
    program. Host dispatch and the device round-trip are paid once per K
    scans instead of once per scan.
    Returns ``pack_hits`` of (vals [K, b, k], idxs [K, b, k]): one array,
    or, where ``download_dtype`` rounds the returned scores (selection
    itself always runs in f32) so that a result-byte-bound link ships
    6 B/hit instead of 8, the pair."""

    def one(q):
        return _streaming_topk_impl(
            mat_t, norms, scales, resid, resid_scales, q,
            k=k, n_items=n_items, cosine=cosine, interpret=interpret, tail=tail,
        )

    vals, idxs = jax.lax.map(one, queries_kb)
    return pack_hits(vals, idxs, download_dtype)


# Scoped VMEM the kernel asks Mosaic for. A v5e core has 128 MiB; the
# default scoped limit of 16 MiB does not hold the [256, 4096] score-tile
# working set next to a 250-feature item block in any dtype (every 250f
# variant was refused with "ran out of memory in memory space vmem",
# PR 21), so the limit is stated, and the tile sizes below plan for
# _VMEM_BUDGET of it: the estimate is coarse, the rest is its headroom.
_VMEM_LIMIT = 64 * 2**20
_VMEM_BUDGET = 40 * 2**20


# grid steps carry the running top-k, so they run in order
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT
)


def _step_bytes(k_feat: int, b: int, tile: int, subtiles: int, dtype_bytes: int) -> int:
    """Estimated VMEM of one grid step: the double-buffered item block
    (feature rows padded to the dtype's sublane tile) and its [1, step]
    aux row (8 sublanes), the f32 copy an int8 sub-tile is upcast to
    before the dot, the query block, and six [b, tile] 4-byte tiles —
    scores, column ids, and the temporaries of one selection round."""
    rows = _ceil_to(k_feat, 8 * (4 // dtype_bytes))
    item = 2 * rows * tile * subtiles * dtype_bytes
    aux = 2 * 8 * tile * subtiles * 4
    upcast = rows * tile * 4 if dtype_bytes == 1 else 0
    return item + aux + upcast + 2 * b * rows * 4 + 6 * b * tile * 4


def _subtiles_for(k_feat: int, b: int, dtype_bytes: int) -> int:
    """Largest power-of-two sub-tile count (<= SUBTILES, divides BLOCK_N)
    whose grid step fits the VMEM budget."""
    s = SUBTILES
    while s > 1 and _step_bytes(k_feat, b, SCORE_TILE, s, dtype_bytes) > _VMEM_BUDGET:
        s //= 2
    return s


def _fold_aux(norms, scales, cosine: bool):
    """The kernel's third operand: item norms (unquantized) or the folded
    dequant multiplier (quantized — cosine divides the cached norms into
    the per-row scale here, outside the kernel, so scoring is one
    multiply either way)."""
    if scales is None:
        return norms
    if cosine:
        return scales / jnp.maximum(norms, 1e-12)
    return scales


def _pad_queries(q, k_feat: int):
    # int8 sublane padding: the handle's feature dim is a 32-multiple;
    # zero-pad queries to match (zero features cannot change any score)
    if q.shape[1] < k_feat:
        q = jnp.pad(q, ((0, 0), (0, k_feat - q.shape[1])))
    return q


def _streaming_topk_impl(
    mat_t, norms, scales, resid, resid_scales, queries, *,
    k, n_items, cosine, interpret, count_rounds=False, n_valid=None, tail=None,
):
    """(vals [b, k], idxs [b, k]) of one scan group. ``count_rounds``
    (trace-time; tests and tools/scan_rounds.py only, no served program
    sets it) appends the kernel's int32 [1, 2] count of
    (score tiles that passed the gate, selection rounds run in them).
    ``n_valid`` (int32 [1], a device value) masks the columns from it up
    where the count is not known when the program is traced: every shard
    of a mesh runs one program on its own rows (ops/topn.py). The kernel
    then reads it from SMEM, and ``n_items`` only sizes the rescore.
    ``tail`` ([t, n_pad] float32; ``tail_rows``) is the split layout's
    second plane, one more streamed operand of the same kernel; without
    it ``mat_t`` holds every feature row, as a handle's does whose rule
    does not engage."""
    k_main, n_pad = mat_t.shape
    k_feat = k_main + (0 if tail is None else tail.shape[0])
    b = queries.shape[0]
    if b > MAX_GROUP_ROWS:
        raise ValueError(f"a scan group holds at most {MAX_GROUP_ROWS} rows, not {b}")
    quantized = scales is not None
    q = _pad_queries(queries.astype(jnp.float32 if quantized else mat_t.dtype), k_feat)
    aux = _fold_aux(norms, scales, cosine)
    m = _scan_k(k, n_items, resid)

    def finish(vals, idxs):
        if resid is None:
            return vals, idxs
        qn = (
            jnp.linalg.norm(q.astype(jnp.float32), axis=1, keepdims=True)
            if cosine
            else None
        )
        return _rescore_topk(
            vals, idxs, q.astype(jnp.float32), qn, resid, resid_scales, norms,
            k=k, cosine=cosine,
        )
    common = {} if interpret else dict(memory_space=pltpu.VMEM)
    smem = {} if interpret else dict(memory_space=pltpu.SMEM)
    # the mask's bound: the static count, or a fourth, SMEM operand
    masked_from = n_items if n_valid is None else None
    valid_spec = [] if n_valid is None else [pl.BlockSpec(**smem)]
    valid_arg = [] if n_valid is None else [n_valid.astype(jnp.int32).reshape(1)]
    # adapt sub-tiles to the feature width so wide models (250-feat) still
    # fit scoped VMEM; n_pad is a BLOCK_N multiple, so any power-of-two
    # divisor of SUBTILES keeps the grid exact. A tail block is counted
    # as a whole sublane tile, which is what the padding rows it replaces
    # took: the split changes no grid.
    subtiles = _subtiles_for(
        k_main + (0 if tail is None else _SUBLANES), b, mat_t.dtype.itemsize
    )
    step = SCORE_TILE * subtiles
    grid = n_pad // step
    tail_spec = (
        [] if tail is None
        else [pl.BlockSpec((tail.shape[0], step), lambda i: (0, i), **common)]
    )
    tail_arg = [] if tail is None else [tail]
    kernel = functools.partial(
        _topn_kernel, k=m, n_items=masked_from, cosine=cosine, quantized=quantized,
        grid=grid, subtiles=subtiles, tailed=tail is not None,
    )
    out_specs = [
        pl.BlockSpec((b, m), lambda i: (0, 0), **common),
        pl.BlockSpec((b, m), lambda i: (0, 0), **common),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, m), jnp.float32),
        jax.ShapeDtypeStruct((b, m), jnp.int32),
    ]
    if count_rounds:
        out_specs.append(pl.BlockSpec(**smem))
        out_shape.append(jax.ShapeDtypeStruct((1, 2), jnp.int32))
    vals, idxs, *counts = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((b, k_feat), lambda i: (0, 0), **common),
            pl.BlockSpec((k_main, step), lambda i: (0, i), **common),
            pl.BlockSpec((1, step), lambda i: (0, i), **common),
            *tail_spec,
            *valid_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((b, _ceil_to(m, _STATE_LANES)), jnp.float32),
            pltpu.VMEM((b, _ceil_to(m, _STATE_LANES)), jnp.int32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="oryx_topn_scan",
    )(q, mat_t, aux, *tail_arg, *valid_arg)
    return (*finish(vals, idxs), *counts)


# -- XLA twin of the blocked scan (non-TPU backends) --------------------------


def _xla_scan_step(n_pad: int) -> int:
    """Largest BLOCK_N multiple that divides ``n_pad``, capped at
    XLA_SCAN_BLOCK — keeps the lax.scan grid exact without re-padding."""
    m = n_pad // BLOCK_N
    d = max(1, min(XLA_SCAN_BLOCK // BLOCK_N, m))
    while m % d:
        d -= 1
    return BLOCK_N * d


def _xla_streaming_topk_impl(
    mat_t, norms, scales, resid, resid_scales, queries, *, k, n_items, cosine,
    n_valid=None, tail=None,
):
    """Fused XLA blocked scan over the feature-major layout: lax.scan
    streams [k_feat, block] item slices and reduces each block on the
    spot, so the [b, n] score matrix never materializes — which is what
    lets scan batches grow past the memory of the naive matmul+top_k
    path. f32/bf16 handles top-k each block exactly and merge the
    [b, grid * k] candidates with one tiny lax.top_k. int8 handles
    (upcast to f32 before the dot — XLA CPU int8 matmul is ~3x slower
    than upcast + f32 GEMM, measured) reduce each block to per-_CHUNK
    maxes instead: the max fuses into the GEMM's epilogue where a wide
    in-scan lax.top_k does not (measured ~2x the scan time), the top-m
    chunks by max provably contain the top-m items, and only those
    chunks' columns gather both int8 planes for an exact ~14-bit rescore
    after the scan. HIGHEST precision keeps the f32 GEMM on the fast CPU
    path (the DEFAULT-precision CPU kernel is ~2x slower, measured).
    ``n_valid``, ``tail``: as in ``_streaming_topk_impl`` (the tail's
    rows are a second small GEMM a block, added to the main plane's)."""
    k_main, n_pad = mat_t.shape
    k_feat = k_main + (0 if tail is None else tail.shape[0])
    b = queries.shape[0]
    quantized = scales is not None
    q = _pad_queries(queries.astype(jnp.float32), k_feat)
    qn = jnp.linalg.norm(q, axis=1, keepdims=True) if cosine else None
    mult = _fold_aux(norms, scales, cosine) if quantized else None
    block = _xla_scan_step(n_pad)
    grid = n_pad // block
    m = _scan_k(k, n_items, resid)
    chunked = (
        quantized
        and resid is not None
        and block % _CHUNK == 0
        and block // _CHUNK >= _chunk_k(k, block // _CHUNK)
    )
    # padding mask as an ADDITIVE bias, not a per-element where: the
    # iota-compare-select breaks the GEMM epilogue fusion and costs ~3x
    # the GEMM itself (measured: +1.1 s/dispatch at 1M x 50); a broadcast
    # add of a constant-folded [-inf over padded cols] row fuses like the
    # scale multiply does. Padded columns are all-zero so their dot is
    # finite (0) and 0 + -inf = -inf, never NaN.
    masked_from = n_items if n_valid is None else n_valid.reshape(())
    bias = jnp.where(
        jnp.arange(n_pad, dtype=jnp.int32) < masked_from, 0.0, -jnp.inf
    )[None, :].astype(jnp.float32)

    def scores_for(i):
        base = i * block
        blk = jax.lax.dynamic_slice(mat_t, (0, base), (k_main, block))
        scores = jnp.dot(
            q[:, :k_main],
            blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        if tail is not None:
            scores = scores + jnp.dot(
                q[:, k_main:],
                jax.lax.dynamic_slice(tail, (0, base), (k_feat - k_main, block)),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
        if quantized:
            scores = scores * jax.lax.dynamic_slice(mult, (0, base), (1, block))
            if cosine:
                scores = scores / jnp.maximum(qn, 1e-12)
        elif cosine:
            nrm = jax.lax.dynamic_slice(norms, (0, base), (1, block))
            scores = scores / jnp.maximum(nrm * qn, 1e-12)
        return scores + jax.lax.dynamic_slice(bias, (0, base), (1, block))

    if not chunked:
        kk = min(k, block)

        def step(carry, i):
            v, p = jax.lax.top_k(scores_for(i), kk)
            return carry, (v, p + i * block)

        _, (vs, idxs) = jax.lax.scan(step, 0, jnp.arange(grid, dtype=jnp.int32))
        # candidates are ordered (block, rank): for equal scores the
        # earlier position is the earlier block / lower item id, and
        # lax.top_k is stable — so ties break by lowest index, same as
        # the kernel
        allv = jnp.moveaxis(vs, 0, 1).reshape(b, grid * kk)
        alli = jnp.moveaxis(idxs, 0, 1).reshape(b, grid * kk)
        vals, pos = jax.lax.top_k(allv, min(k, allv.shape[1]))
        return vals, jnp.take_along_axis(alli, pos, axis=1)

    chunks = block // _CHUNK
    kc = _chunk_k(k, chunks)

    def step(carry, i):
        cm = jnp.max(scores_for(i).reshape(b, chunks, _CHUNK), axis=2)
        v, p = jax.lax.top_k(cm, kc)
        return carry, (v, p + i * chunks)

    _, (vs, cps) = jax.lax.scan(step, 0, jnp.arange(grid, dtype=jnp.int32))
    poolv = jnp.moveaxis(vs, 0, 1).reshape(b, grid * kc)
    pooli = jnp.moveaxis(cps, 0, 1).reshape(b, grid * kc)
    return _chunk_tail(
        mat_t, resid, scales, resid_scales, norms, q, qn, poolv, pooli,
        k=k, kc=kc, n_items=masked_from, cosine=cosine,
    )


def _gathered_pair_scores(
    mat_t, resid, scales, resid_scales, norms, q, qn, iid, *, cosine
):
    """Exact ~14-bit two-plane scores for an explicit candidate column set
    ``iid`` [b, m]: gather BOTH int8 planes for just those columns and
    combine ``d1*s1 + d2*s2``. Shared by the chunked scan's candidate tail
    and the IVF tier's probed-cell scan (ops/ivf.py) — sharing the exact
    arithmetic (same gather layout, same einsum contraction) is what lets
    a full-probe IVF scan reproduce the exact path's scores bit-for-bit."""
    c1 = jnp.take(mat_t, iid, axis=1).astype(jnp.float32)  # [kf, b, m]
    c2 = jnp.take(resid, iid, axis=1).astype(jnp.float32)
    d1 = jnp.einsum("bf,fbm->bm", q, c1, precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.einsum("bf,fbm->bm", q, c2, precision=jax.lax.Precision.HIGHEST)
    sc = d1 * scales[0][iid] + d2 * resid_scales[0][iid]
    if cosine:
        sc = sc / jnp.maximum(norms[0][iid] * qn, 1e-12)
    return sc


def _chunk_tail(
    mat_t, resid, scales, resid_scales, norms, q, qn, poolv, pooli, *,
    k, kc, n_items, cosine,
):
    """Candidate stage of the chunked scan: keep the globally best chunks
    from the pooled per-block chunk maxes, gather BOTH int8 planes for
    just their columns, and pick the final top-k from exact ~14-bit
    two-plane scores."""
    b = q.shape[0]
    mc = min(kc, poolv.shape[1])
    _, sel = jax.lax.top_k(poolv, mc)
    # ascending chunk ids -> ascending item ids, so the stable final
    # top_k keeps breaking ties toward the lowest item id
    cid = jnp.sort(jnp.take_along_axis(pooli, sel, axis=1), axis=1)
    iid = (
        cid[:, :, None] * _CHUNK + jnp.arange(_CHUNK, dtype=jnp.int32)[None, None, :]
    ).reshape(b, mc * _CHUNK)
    sc = _gathered_pair_scores(
        mat_t, resid, scales, resid_scales, norms, q, qn, iid, cosine=cosine
    )
    sc = jnp.where(iid < n_items, sc, -jnp.inf)
    v, pos = jax.lax.top_k(sc, k)
    return v, jnp.take_along_axis(iid, pos, axis=1)


def _xla_streaming_topk_multi_impl(
    mat_t, norms, scales, resid, resid_scales, q_kbf, *, k, n_items, cosine, tail=None
):
    """K fused scans sharing ONE pass of int8->f32 block conversion. The
    naive multi path (lax.map of the single impl) re-converts every item
    block once per query group, and at wide features that conversion is
    ~50% on top of the pure f32 GEMM (measured per-block 15.5 ms mixed
    vs 10.4 ms f32 x f32 at 256x16384) — so the loops invert here: the
    lax.scan over blocks is OUTSIDE and the K group GEMMs unroll INSIDE
    the step, all reading the same materialized f32 block. Per-group
    score tiles stay [b, block] (the merged [K*b, block] tile blows the
    LLC — measured 3x slowdown at 512 rows), and the candidate tails
    stay per-group after the scan. Non-chunked handles (f32/bf16, tiny
    matrices) keep the exact lax.map path; only they can have a ``tail``."""
    kg, b, _ = q_kbf.shape
    k_feat, n_pad = mat_t.shape
    block = _xla_scan_step(n_pad)
    grid = n_pad // block
    chunks = block // _CHUNK
    chunked = (
        scales is not None
        and resid is not None
        and block % _CHUNK == 0
        and chunks >= _chunk_k(k, chunks)
    )
    if not chunked:
        def one(q):
            return _xla_streaming_topk_impl(
                mat_t, norms, scales, resid, resid_scales, q,
                k=k, n_items=n_items, cosine=cosine, tail=tail,
            )

        return jax.lax.map(one, q_kbf)

    kc = _chunk_k(k, chunks)
    q_k = _pad_queries(
        q_kbf.astype(jnp.float32).reshape(kg * b, -1), k_feat
    ).reshape(kg, b, k_feat)
    qn_k = (
        jnp.linalg.norm(q_k, axis=2, keepdims=True) if cosine else [None] * kg
    )
    mult = _fold_aux(norms, scales, cosine)
    bias = jnp.where(
        jnp.arange(n_pad, dtype=jnp.int32) < n_items, 0.0, -jnp.inf
    )[None, :].astype(jnp.float32)

    def step(carry, i):
        base = i * block
        blk = jax.lax.dynamic_slice(
            mat_t, (0, base), (k_feat, block)
        ).astype(jnp.float32)
        m_b = jax.lax.dynamic_slice(mult, (0, base), (1, block))
        bia = jax.lax.dynamic_slice(bias, (0, base), (1, block))
        vs, ps = [], []
        for g in range(kg):  # static unroll: kg GEMMs share blk
            sc = (
                jnp.dot(
                    q_k[g], blk,
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )
                * m_b
            )
            if cosine:
                sc = sc / jnp.maximum(qn_k[g], 1e-12)
            cm = jnp.max((sc + bia).reshape(b, chunks, _CHUNK), axis=2)
            v, p = jax.lax.top_k(cm, kc)
            vs.append(v)
            ps.append(p + i * chunks)
        return carry, (jnp.stack(vs), jnp.stack(ps))

    _, (vs, cps) = jax.lax.scan(step, 0, jnp.arange(grid, dtype=jnp.int32))
    poolv = jnp.transpose(vs, (1, 2, 0, 3)).reshape(kg, b, grid * kc)
    pooli = jnp.transpose(cps, (1, 2, 0, 3)).reshape(kg, b, grid * kc)
    outs = [
        _chunk_tail(
            mat_t, resid, scales, resid_scales, norms, q_k[g], qn_k[g],
            poolv[g], pooli[g], k=k, kc=kc, n_items=n_items, cosine=cosine,
        )
        for g in range(kg)
    ]
    return jnp.stack([v for v, _ in outs]), jnp.stack([i for _, i in outs])


@functools.partial(
    jax.jit, static_argnames=("k", "n_items", "cosine", "download_dtype")
)
def _xla_streaming_topk_multi(
    mat_t, norms, scales, resid, resid_scales, queries_kb, *,
    k, n_items, cosine, download_dtype=None, tail=None,
):
    vals, idxs = _xla_streaming_topk_multi_impl(
        mat_t, norms, scales, resid, resid_scales, queries_kb,
        k=k, n_items=n_items, cosine=cosine, tail=tail,
    )
    return pack_hits(vals, idxs, download_dtype)


@functools.partial(
    jax.jit, static_argnames=("k", "n_items", "cosine", "download_dtype")
)
def _xla_streaming_topk_multi_indexed(
    mat_t, norms, scales, resid, resid_scales, x_dev, idx_kb, *,
    k, n_items, cosine, download_dtype=None, tail=None,
):
    vals, idxs = _xla_streaming_topk_multi_impl(
        mat_t, norms, scales, resid, resid_scales,
        x_dev[idx_kb].astype(jnp.float32),
        k=k, n_items=n_items, cosine=cosine, tail=tail,
    )
    return pack_hits(vals, idxs, download_dtype)


def _use_xla_scan(interpret) -> bool:
    """Non-TPU backends with no explicit interpret request run the XLA
    twin of the blocked scan; ``interpret=True`` always forces the Pallas
    interpreter (the parity test suite), and TPU compiles the kernel."""
    return interpret is None and jax.default_backend() != "tpu"


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_items", "cosine", "interpret", "download_dtype"),
)
def _streaming_topk_multi_indexed(
    mat_t, norms, scales, resid, resid_scales, x_dev, idx_kb, *,
    k, n_items, cosine, interpret, download_dtype=None, tail=None,
):
    """Index-submitted fused multi-scan: gather the [K, b, feat] query
    group from the device-resident ``x_dev`` inside the dispatch, then
    run the same per-group pallas scan."""

    def one(idx_b):
        q = x_dev[idx_b].astype(jnp.float32)
        return _streaming_topk_impl(
            mat_t, norms, scales, resid, resid_scales, q,
            k=k, n_items=n_items, cosine=cosine, interpret=interpret, tail=tail,
        )

    vals, idxs = jax.lax.map(one, idx_kb)
    return pack_hits(vals, idxs, download_dtype)


def group_rows(rows: np.ndarray, scan_batch: int = MAX_GROUP_ROWS) -> np.ndarray:
    """[n, ...] rows -> [groups, b, ...] with b = min(scan_batch, n), the
    last group zero-padded: the shape every scan program takes."""
    n = rows.shape[0]
    b = max(1, min(scan_batch, n))
    groups = -(-n // b)
    if groups * b != n:
        pad = np.zeros((groups * b - n,) + rows.shape[1:], rows.dtype)
        rows = np.concatenate([rows, pad])
    return rows.reshape((groups, b) + rows.shape[1:])


def scan_groups(
    up: StreamingItemMatrix,
    groups: np.ndarray | jax.Array,
    k: int,
    cosine: bool = False,
    interpret: bool | None = None,
    download_dtype=None,
    x_dev: jax.Array | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """``pack_hits`` of (scores [K, b, k], indices [K, b, k]) on the
    device (``split_hits`` gives the two), K scans of the whole matrix in
    one dispatch: the one entry of the exact scan.
    ``groups`` is [K, b, feat] query vectors or, with ``x_dev`` (a query
    matrix staged on the device), [K, b] int32 rows of it, so that the
    uplink carries 4 B a query; a NumPy array goes in as it is (the
    jitted call transfers it: no ``device_put`` of the caller's).
    ``interpret=None`` picks per backend: the compiled kernel on TPU, the
    fused XLA blocked scan elsewhere."""
    planes = (up.mat_t, up.norms, up.scales, up.resid, up.resid_scales)
    queries = (groups,) if x_dev is None else (x_dev, groups)
    shared = dict(
        k=max(1, min(int(k), up.n_items)), n_items=up.n_items, cosine=cosine,
        download_dtype=download_dtype, tail=up.tail,
    )
    if _use_xla_scan(interpret):
        fn = _xla_streaming_topk_multi if x_dev is None else _xla_streaming_topk_multi_indexed
        return fn(*planes, *queries, **shared)
    fn = _streaming_topk_multi if x_dev is None else _streaming_topk_multi_indexed
    return fn(*planes, *queries, interpret=bool(interpret), **shared)


def top_k_streaming_device(
    up: StreamingItemMatrix,
    queries: np.ndarray,
    k: int,
    cosine: bool = False,
    interpret: bool | None = None,
    download_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """(scores [b, k], indices [b, k]) as device arrays for [b, feat]
    query vectors (tests and tools; serving submits through ops/topn.py)."""
    q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
    vals, idxs = split_hits(scan_groups(
        up, group_rows(q), k, cosine=cosine, interpret=interpret,
        download_dtype=download_dtype,
    ))
    kk = vals.shape[-1]
    return vals.reshape(-1, kk)[: len(q)], idxs.reshape(-1, kk)[: len(q)]


def top_k_streaming(
    up: StreamingItemMatrix,
    queries: np.ndarray,
    k: int,
    cosine: bool = False,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """(indices [b, k], scores [b, k]) of the best items per query row."""
    vals, idxs = top_k_streaming_device(up, queries, k, cosine=cosine, interpret=interpret)
    return np.asarray(idxs), np.asarray(vals)
