"""Top-N scoring: batched matvec + top_k on device.

Replaces the reference's per-request thread-pool scan over LSH partitions
(ALSServingModel.topN / TopNConsumer.java, VectorMath.dot in the hot
loop): dot scores for ALL items are computed on the MXU and top-k
selected on device. Two device backends share one public API:

- ``xla``: plain ``scores = Q @ Y.T`` + ``lax.top_k`` — simple, fine for
  small/medium item matrices;
- ``pallas`` (TPU): the fused streaming kernel in
  :mod:`oryx_tpu.ops.pallas_topn`, which never materializes the [b, n]
  score matrix in HBM and can hold items in bfloat16 — 2-6x less HBM
  traffic at 1M+ items.

``upload`` picks the backend (pallas when running on TPU, xla
otherwise); ``top_k_scores`` / ``top_k_scores_batch`` dispatch on the
uploaded handle's type.

``submit_top_k`` is the async form: it enqueues the device computation
and a non-blocking device→host copy, returning a handle whose
``result()`` materializes the answer. Callers that keep several requests
in flight (the serving layer's request pipeline, bench.py) overlap
device compute and host<->device transfers instead of paying a full
round-trip per request.
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.ops import ivf as ivf_ops
from oryx_tpu.ops.ivf import IVFIndex
from oryx_tpu.ops.pallas_topn import (
    StreamingItemMatrix,
    _is_int8,
    _quantize_residual,
    _quantize_rows,
    top_k_streaming,
    top_k_streaming_device,
    top_k_streaming_device_multi,
    upload_streaming,
)


log = logging.getLogger(__name__)


def _default_streaming() -> bool:
    return jax.default_backend() == "tpu"


def upload(
    matrix: np.ndarray,
    dtype=None,
    streaming: bool | None = None,
):
    """Move a packed [n, k] float32 item matrix to device.

    Returns an opaque handle for the top-k functions. On TPU the handle
    is a :class:`StreamingItemMatrix` (feature-major layout for the
    Pallas kernel, optionally bfloat16); elsewhere it is the plain
    ``(matrix, norms)`` device pair for the XLA path.

    ``dtype=int8`` returns the streaming (feature-major, row-quantized)
    handle on EVERY backend: the quantized scan engine owns that layout,
    and non-TPU backends scan it with the fused XLA twin of the kernel
    rather than materializing [b, n] scores.
    """
    if _is_int8(dtype):
        return upload_streaming(matrix, dtype=jnp.int8)
    if streaming is None:
        streaming = _default_streaming()
    if streaming:
        return upload_streaming(matrix, dtype=dtype or jnp.float32)
    mat = jnp.asarray(matrix, dtype=dtype or jnp.float32)
    norms = jnp.linalg.norm(mat.astype(jnp.float32), axis=1)
    return mat, norms


def _dot_precision(dtype):
    """f32 scoring gets true f32 MXU accumulation — the TPU default would
    silently drop f32 matmuls to bf16 passes, making the "exact" XLA path
    *less* precise than the Pallas kernel it is the reference twin for.
    bf16 inputs stay on the intentional fast path."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


@functools.partial(jax.jit, static_argnums=2)
def _dot_topk(mat, query, k):
    scores = jnp.dot(
        mat, query, preferred_element_type=jnp.float32, precision=_dot_precision(mat.dtype)
    )
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnums=3)
def _cosine_topk(mat, norms, query, k):
    qn = jnp.linalg.norm(query.astype(jnp.float32))
    scores = jnp.dot(
        mat, query, preferred_element_type=jnp.float32, precision=_dot_precision(mat.dtype)
    ) / jnp.maximum(norms * qn, 1e-12)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _dot_topk_batch(mat, norms, queries, k, cosine, download_dtype=None):
    scores = jnp.dot(
        queries, mat.T, preferred_element_type=jnp.float32, precision=_dot_precision(mat.dtype)
    )  # [b, n]
    if cosine:
        qn = jnp.linalg.norm(queries.astype(jnp.float32), axis=1, keepdims=True)
        scores = scores / jnp.maximum(norms[None, :] * qn, 1e-12)
    vals, idxs = jax.lax.top_k(scores, k)
    if download_dtype is not None:
        vals = vals.astype(download_dtype)
    return vals, idxs


def top_k_scores(uploaded, query: np.ndarray, k: int, cosine: bool = False):
    """(indices, scores) of the k best items for one query vector."""
    if isinstance(uploaded, IVFIndex):
        idx, vals = ivf_ops.top_k(uploaded, query, k, cosine=cosine)
        return idx[0], vals[0]
    if isinstance(uploaded, StreamingItemMatrix):
        idx, vals = top_k_streaming(uploaded, query, k, cosine=cosine)
        return idx[0], vals[0]
    mat, norms = uploaded
    k = max(1, min(int(k), mat.shape[0]))
    q = jnp.asarray(query, dtype=mat.dtype)
    if cosine:
        s, i = _cosine_topk(mat, norms, q, k)
    else:
        s, i = _dot_topk(mat, q, k)
    return np.asarray(i), np.asarray(s)


def top_k_scores_batch(uploaded, queries: np.ndarray, k: int, cosine: bool = False):
    """Batched top-k for [b, k] query vectors (concurrent requests)."""
    if isinstance(uploaded, IVFIndex):
        return ivf_ops.top_k(uploaded, queries, k, cosine=cosine)
    if isinstance(uploaded, StreamingItemMatrix):
        return top_k_streaming(uploaded, queries, k, cosine=cosine)
    mat, norms = uploaded
    k = max(1, min(int(k), mat.shape[0]))
    q = jnp.asarray(queries, dtype=mat.dtype)
    s, i = _dot_topk_batch(mat, norms, q, k, cosine)
    return np.asarray(i), np.asarray(s)


# -- mesh-sharded scan --------------------------------------------------------


@dataclass
class ShardedItemMatrix:
    """Item matrix row-sharded over a device mesh: each device holds an
    [n/d, k] slice plus its norms. The multi-chip serving layout — a
    40M x 200 f32 model is 32 GB replicated but 2 GB/chip on a v5e-16
    (SURVEY §2.12 request parallelism; the reference shards the same way
    across LSH thread partitions on one host)."""

    mat: jax.Array  # [n_pad, k], rows sharded over 'data'; f32/bf16/int8
    norms: jax.Array  # [n_pad], sharded alike
    n_items: int
    mesh: object
    scales: jax.Array | None = None  # [n_pad] per-row int8 dequant scale
    resid: jax.Array | None = None  # [n_pad, k] int8 residual plane
    resid_scales: jax.Array | None = None  # [n_pad] residual dequant scale


def upload_sharded(matrix: np.ndarray, mesh, dtype=None) -> ShardedItemMatrix:
    """Shard a packed [n, k] item matrix row-wise over `mesh`'s devices
    (padded so every device gets an equal slice). ``dtype=int8``
    row-quantizes exactly like the streaming handle: int8 codes sharded
    with the rows, one f32 scale per row riding next to the norms."""
    from oryx_tpu.parallel.mesh import (
        data_sharding,
        pad_to_multiple,
        shard_layout,
        shard_rows,
    )

    n, k = matrix.shape
    d = mesh.devices.size
    n_pad = pad_to_multiple(max(n, d), d)
    mat = np.zeros((n_pad, k), dtype=np.float32)
    mat[:n] = matrix
    norms = np.linalg.norm(mat, axis=1)
    if _is_int8(dtype):
        q, s = _quantize_rows(mat)  # pad rows are all-zero -> scale 1.0
        q2, s2 = _quantize_residual(mat, q, s)
        up = ShardedItemMatrix(
            mat=jax.device_put(jnp.asarray(q), data_sharding(mesh, 2)),
            norms=jax.device_put(jnp.asarray(norms), shard_rows(mesh)),
            n_items=n,
            mesh=mesh,
            scales=jax.device_put(jnp.asarray(s), shard_rows(mesh)),
            resid=jax.device_put(jnp.asarray(q2), data_sharding(mesh, 2)),
            resid_scales=jax.device_put(jnp.asarray(s2), shard_rows(mesh)),
        )
    else:
        up = ShardedItemMatrix(
            mat=jax.device_put(
                jnp.asarray(mat, dtype=dtype or jnp.float32), data_sharding(mesh, 2)
            ),
            norms=jax.device_put(jnp.asarray(norms), shard_rows(mesh)),
            n_items=n,
            mesh=mesh,
        )
    log.info("sharded item matrix, %d items, shards: %s", n, shard_layout(up.mat))
    return up


def _sharded_topk_fn(mesh, k: int, cosine: bool, quantized: bool = False):
    """shard_map'd scan: each device scores and top-k's its row shard,
    then the tiny [b, k]-per-device candidates all-gather and a final
    top-k merges them — the [b, n] score matrix never materializes
    globally and no full-matrix collective ever runs. Quantized shards
    upcast their int8 slice in-register and dequantize by the sharded
    per-row scale after the dot."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from oryx_tpu.parallel.mesh import DATA_AXIS

    def local(mat, norms, scales, resid, resid_scales, queries, qn, shard_base, n_items_arr):
        # mat: [n_local, k_feat]; shard_base: [1] global row offset;
        # scales/resid/resid_scales: per-row dequant multipliers and the
        # int8 residual plane (norms/mat dummies with the same sharding
        # when not quantized, ignored below). Sharded scans sum both int8
        # planes in full — per-shard candidate gathers aren't worth the
        # collective plumbing, and the shards split the extra GEMM anyway.
        m = mat.astype(jnp.float32) if quantized else mat
        scores = jnp.dot(
            queries, m.T, preferred_element_type=jnp.float32,
            precision=_dot_precision(m.dtype),
        )  # [b, n_local]
        if quantized:
            scores = scores * scales[None, :]
            scores = scores + jnp.dot(
                queries, resid.astype(jnp.float32).T,
                preferred_element_type=jnp.float32,
                precision=_dot_precision(mat.dtype),
            ) * resid_scales[None, :]
        if cosine:
            scores = scores / jnp.maximum(norms[None, :] * qn, 1e-12)
        # mask padding by global row position — NOT by zero norms, which
        # would also drop genuine zero-vector items (cold rows score 0,
        # same as the single-device path)
        gcol = shard_base[0] + jnp.arange(mat.shape[0], dtype=jnp.int32)
        scores = jnp.where(gcol[None, :] < n_items_arr[0], scores, -jnp.inf)
        kk = min(k, mat.shape[0])
        v, i = jax.lax.top_k(scores, kk)
        i = i + shard_base[0]
        # gather every device's candidates and merge: [b, d*kk] is tiny
        v_all = jax.lax.all_gather(v, DATA_AXIS, axis=1, tiled=True)
        i_all = jax.lax.all_gather(i, DATA_AXIS, axis=1, tiled=True)
        vm, pos = jax.lax.top_k(v_all, min(k, v_all.shape[1]))
        im = jnp.take_along_axis(i_all, pos, axis=1)
        return vm, im

    in_specs = (
        P(DATA_AXIS, None),
        P(DATA_AXIS),
        P(DATA_AXIS),  # per-row scales (or the norms dummy)
        P(DATA_AXIS, None),  # residual plane (or the mat dummy)
        P(DATA_AXIS),  # residual scales (or the norms dummy)
        P(),  # queries replicated
        P(),
        P(DATA_AXIS),
        P(),  # n_items replicated
    )
    out_specs = (P(), P())
    # after the all_gather every device computes the same merge, but the
    # replication checker can't infer that through top_k — disable it
    smapped = shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return jax.jit(smapped)


def top_k_sharded(
    up: ShardedItemMatrix, queries: np.ndarray, k: int, cosine: bool = False
) -> tuple[np.ndarray, np.ndarray]:
    """(indices [b, k], scores [b, k]) over the mesh-sharded matrix."""
    q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
    k = max(1, min(int(k), up.n_items))
    qn = np.linalg.norm(q, axis=1, keepdims=True).astype(np.float32)
    d = up.mesh.devices.size
    per = up.mat.shape[0] // d
    shard_base = jnp.arange(d, dtype=jnp.int32) * per
    quantized = up.scales is not None
    fn = _sharded_topk_cache(up.mesh, k, bool(cosine), quantized)
    vals, idxs = fn(
        up.mat,
        up.norms,
        up.scales if quantized else up.norms,
        up.resid if quantized else up.mat,
        up.resid_scales if quantized else up.norms,
        jnp.asarray(q, dtype=jnp.float32 if quantized else up.mat.dtype),
        jnp.asarray(qn),
        shard_base,
        jnp.asarray([up.n_items], dtype=jnp.int32),
    )
    return np.asarray(idxs), np.asarray(vals)


_sharded_fns: dict = {}


def _sharded_topk_cache(mesh, k: int, cosine: bool, quantized: bool = False):
    key = (id(mesh), k, cosine, quantized)
    fn = _sharded_fns.get(key)
    if fn is None:
        fn = _sharded_fns[key] = _sharded_topk_fn(mesh, k, cosine, quantized)
    return fn


# -- incremental updates ------------------------------------------------------


# No donation: in-flight top-k requests may still hold the previous
# handle, and donating would delete their buffers mid-request. The
# device-side copy this costs is HBM-internal (no host transfer — the
# thing incremental refresh exists to avoid) and transient.
@jax.jit
def _scatter_rows_t(mat_t, norms, rows, vals, new_norms):
    """Feature-major scatter: mat_t[:, rows] <- vals.T, norms[0, rows] <- n."""
    mat_t = mat_t.at[:, rows].set(vals.T.astype(mat_t.dtype))
    norms = norms.at[0, rows].set(new_norms)
    return mat_t, norms


@jax.jit
def _scatter_rows(mat, norms, rows, vals, new_norms):
    mat = mat.at[rows].set(vals.astype(mat.dtype))
    norms = norms.at[rows].set(new_norms)
    return mat, norms


@jax.jit
def _scatter_rows_t_q(
    mat_t, norms, scales, resid, resid_scales, rows, q, s, q2, s2, new_norms
):
    """int8 feature-major scatter of pre-quantized rows: codes + residual
    codes + norms + both per-row scales in one call. Quantization happens
    on the HOST (``_quantize_rows``/``_quantize_residual``, the same
    functions upload uses) so a speed-layer fold-in that touches a row
    leaves it bit-identical to a fresh upload of the same values — under
    jit, XLA fuses the requantize arithmetic into FMAs and drifts a few
    ulps from the host result."""
    kf_pad = mat_t.shape[0]

    def pad_t(codes):
        codes = codes.T
        if codes.shape[0] < kf_pad:  # int8 sublane padding on the handle
            codes = jnp.pad(codes, ((0, kf_pad - codes.shape[0]), (0, 0)))
        return codes

    mat_t = mat_t.at[:, rows].set(pad_t(q))
    resid = resid.at[:, rows].set(pad_t(q2))
    norms = norms.at[0, rows].set(new_norms)
    scales = scales.at[0, rows].set(s)
    resid_scales = resid_scales.at[0, rows].set(s2)
    return mat_t, norms, scales, resid, resid_scales


def capacity(uploaded) -> int:
    """Row capacity of the handle (padding included); rows beyond
    ``n_items`` can be appended in place on the streaming layout. For an
    IVF handle it is the built catalog plus the free overlay slots —
    overflow forces a rebuild, which is exactly when the routing table
    should be refreshed anyway."""
    if isinstance(uploaded, IVFIndex):
        return ivf_ops.capacity(uploaded)
    if isinstance(uploaded, StreamingItemMatrix):
        return uploaded.mat_t.shape[1]
    mat, _ = uploaded
    return mat.shape[0]


def update_rows(uploaded, rows: np.ndarray, values: np.ndarray, n_items: int | None = None):
    """Scatter-update `rows` of an uploaded item matrix with `values`
    [len(rows), k] — the incremental-refresh path (SURVEY §7
    'incremental serving state vs immutable device arrays'): a handful
    of dirty vectors ship a few KB host->device instead of the whole
    matrix. For the streaming layout, `n_items` may grow into the padded
    capacity (append of new items without realloc).

    The row-count is bucketed to a power of two (padding repeats the last
    row) so jit retraces O(log n) scatter shapes, not one per batch size.
    """
    if isinstance(uploaded, IVFIndex):
        # IVF fold-ins route through the pending overlay (scanned exactly
        # by every query); IVFOverlayFull propagates so the caller can
        # fall back to a full rebuild
        return ivf_ops.update_rows(uploaded, rows, values, n_items=n_items)
    rows = np.asarray(rows, dtype=np.int32)
    values = np.ascontiguousarray(values, dtype=np.float32)
    m = len(rows)
    if m == 0:
        return uploaded
    bucket = 1 << (m - 1).bit_length()
    if bucket != m:
        pad = bucket - m
        rows = np.concatenate([rows, np.repeat(rows[-1:], pad)])
        values = np.concatenate([values, np.repeat(values[-1:], pad, axis=0)])
    new_norms = np.linalg.norm(values, axis=1)
    if isinstance(uploaded, StreamingItemMatrix):
        count = uploaded.n_items if n_items is None else n_items
        if uploaded.scales is not None:
            # quantized handle: touched rows requantize in place — the
            # speed-layer fold-in path never falls back to a full upload
            qr, sr = _quantize_rows(values)
            q2r, s2r = _quantize_residual(values, qr, sr)
            mat_t, norms, scales, resid, resid_scales = _scatter_rows_t_q(
                uploaded.mat_t, uploaded.norms, uploaded.scales,
                uploaded.resid, uploaded.resid_scales, rows,
                qr, sr, q2r, s2r, new_norms,
            )
            return StreamingItemMatrix(
                mat_t=mat_t, norms=norms, n_items=count,
                scales=scales, features=uploaded.features,
                resid=resid, resid_scales=resid_scales,
            )
        mat_t, norms = _scatter_rows_t(
            uploaded.mat_t, uploaded.norms, rows, values, new_norms
        )
        return StreamingItemMatrix(mat_t=mat_t, norms=norms, n_items=count)
    mat, norms = uploaded
    return _scatter_rows(mat, norms, rows, values, new_norms)


@dataclass
class TopNHandle:
    """In-flight async top-k request; ``result()`` blocks and returns
    (indices [b, k], scores [b, k]) as numpy arrays."""

    _vals: jax.Array
    _idxs: jax.Array

    def result(self) -> tuple[np.ndarray, np.ndarray]:
        # scores may travel as bf16 (download_dtype); callers always see f32
        return np.asarray(self._idxs), np.asarray(self._vals).astype(np.float32, copy=False)


@dataclass
class MultiTopNHandle:
    """In-flight fused multi-scan request; ``result()`` returns
    (indices [n, k], scores [n, k]) for the original n queries."""

    _vals: jax.Array  # [K, b, k]
    _idxs: jax.Array
    _n: int

    def result(self) -> tuple[np.ndarray, np.ndarray]:
        k = self._vals.shape[-1]
        idxs = np.asarray(self._idxs).reshape(-1, k)[: self._n]
        vals = (
            np.asarray(self._vals)
            .astype(np.float32, copy=False)  # bf16-on-the-wire -> f32 for callers
            .reshape(-1, k)[: self._n]
        )
        return idxs, vals


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _dot_topk_batch_multi(mat, norms, queries_kb, k, cosine, download_dtype=None):
    """XLA twin of the fused multi-scan: lax.map over query groups keeps
    peak memory at one [b, n] score block instead of [K*b, n]."""

    def one(q):
        return _dot_topk_batch(mat, norms, q, k, cosine)

    vals, idxs = jax.lax.map(one, queries_kb)
    if download_dtype is not None:
        vals = vals.astype(download_dtype)
    return vals, idxs


def _auto_download_dtype(uploaded) -> object | None:
    """Scores of a bf16 item matrix carry ~bf16 information even though
    selection accumulates in f32 — shipping them back over a result-byte-
    bound link as bf16 cuts the per-hit payload from 8 B to 6 B without
    changing the on-device ranking. f32 matrices keep f32 results."""
    mat = uploaded.mat_t if isinstance(uploaded, StreamingItemMatrix) else uploaded[0]
    # int8 scores carry ~0.4% quantization error already — bf16 wire dtype
    # loses nothing that selection kept
    return jnp.bfloat16 if mat.dtype in (jnp.bfloat16, jnp.int8) else None


def _group_pad(arr: np.ndarray, scan_batch: int) -> tuple[np.ndarray, int]:
    """Zero-pad rows to a multiple of the per-scan batch and reshape to
    [groups, b, ...]; returns (grouped, real row count)."""
    n = arr.shape[0]
    b = max(1, min(scan_batch, n))
    groups = (n + b - 1) // b
    if groups * b != n:
        pad = np.zeros((groups * b - n,) + arr.shape[1:], arr.dtype)
        arr = np.concatenate([arr, pad])
    return arr.reshape((groups, b) + arr.shape[1:]), n


def _async_multi_handle(vals, idxs, n: int) -> MultiTopNHandle:
    """Enqueue the device→host copies without blocking and wrap."""
    vals.copy_to_host_async()
    idxs.copy_to_host_async()
    return MultiTopNHandle(vals, idxs, n)


def submit_top_k_multi(
    uploaded,
    queries: np.ndarray,
    k: int,
    cosine: bool = False,
    scan_batch: int = 256,
    nprobe: int | None = None,
) -> MultiTopNHandle:
    """Fused form of submit_top_k: ceil(n / scan_batch) full-matrix scans
    run inside ONE device dispatch (lax.map), so per-dispatch host work
    and device round-trip latency amortize across the whole query group.
    This is what converts a dispatch-bound serving pipeline (~hundreds of
    scans/s regardless of batch size) into a bandwidth/MXU-bound one.
    scan_batch bounds per-scan VMEM ([scan_batch, BLOCK_N] f32 scores)."""
    q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
    if isinstance(uploaded, IVFIndex):
        # the IVF program does its own QUERY_BLOCK grouping (lax.map over
        # groups inside one dispatch), so the whole batch submits at once.
        # `nprobe` overrides the index default per call (overload control's
        # reduced-probe rung); ignored for non-IVF handles below, which
        # have no probe concept.
        vals, ids = ivf_ops.top_k_device(uploaded, q, k, cosine=cosine, nprobe=nprobe)
        return _async_multi_handle(vals[None], ids[None], q.shape[0])
    q_kb, n = _group_pad(q, scan_batch)
    dl = _auto_download_dtype(uploaded)
    if isinstance(uploaded, StreamingItemMatrix):
        vals, idxs = top_k_streaming_device_multi(
            uploaded, jnp.asarray(q_kb), k, cosine=cosine, download_dtype=dl
        )
    else:
        mat, norms = uploaded
        kk = max(1, min(int(k), mat.shape[0]))
        vals, idxs = _dot_topk_batch_multi(
            mat, norms, jnp.asarray(q_kb, dtype=mat.dtype), kk, cosine, dl
        )
    return _async_multi_handle(vals, idxs, n)


def upload_queries(queries: np.ndarray) -> jax.Array:
    """Stage a [m, feat] query-vector matrix on device (float32), for
    index-submitted scans."""
    return jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))


def upload_random(
    n_items: int,
    num_features: int,
    dtype=None,
    seed: int = 0,
    streaming: bool | None = None,
):
    """Benchmark helper: a random item matrix generated ON DEVICE, in the
    same handle form as :func:`upload`. A 20M x 250 bf16 matrix is 10 GB;
    generating it device-side means those bytes never cross the
    host<->device link and never cost host RAM."""
    if streaming is None:
        streaming = _default_streaming()
    dtype = dtype or jnp.float32
    key = jax.random.PRNGKey(seed)
    if _is_int8(dtype):
        # int8 is always the streaming layout: generate f32 feature-major
        # on device, then quantize per column (= per item row) in place
        from oryx_tpu.ops.pallas_topn import _INT8_FEAT_MULTIPLE, BLOCK_N, _ceil_to

        n_pad = max(BLOCK_N, ((n_items + BLOCK_N - 1) // BLOCK_N) * BLOCK_N)
        mat_t, norms = _gen_streaming_random(
            key, num_features, n_pad, n_items, jnp.float32
        )
        mat_q, scales, mat_r, rscales = _quantize_cols_t(mat_t)
        kf_pad = _ceil_to(num_features, _INT8_FEAT_MULTIPLE)
        if kf_pad != num_features:
            mat_q = jnp.pad(mat_q, ((0, kf_pad - num_features), (0, 0)))
            mat_r = jnp.pad(mat_r, ((0, kf_pad - num_features), (0, 0)))
        return StreamingItemMatrix(
            mat_t=mat_q, norms=norms, n_items=n_items, scales=scales,
            features=num_features if kf_pad != num_features else None,
            resid=mat_r, resid_scales=rscales,
        )
    if streaming:
        from oryx_tpu.ops.pallas_topn import BLOCK_N

        n_pad = max(BLOCK_N, ((n_items + BLOCK_N - 1) // BLOCK_N) * BLOCK_N)
        mat_t, norms = _gen_streaming_random(key, num_features, n_pad, n_items, dtype)
        return StreamingItemMatrix(mat_t=mat_t, norms=norms, n_items=n_items)
    mat, norms = _gen_plain_random(key, n_items, num_features, dtype)
    return mat, norms


@functools.partial(jax.jit, donate_argnums=0, static_argnums=3)
def _fill_normal_block(buf, key, start, width):
    blk = jax.random.normal(key, (buf.shape[0], width), dtype=buf.dtype)
    return jax.lax.dynamic_update_slice(buf, blk, (0, start))


@functools.partial(jax.jit, donate_argnums=0, static_argnums=2)
def _mask_and_norms(mat_t, n_items_arr, n_pad):
    mask = (jnp.arange(n_pad) < n_items_arr)[None, :]
    mat_t = jnp.where(mask, mat_t, jnp.zeros((), dtype=mat_t.dtype))
    norms = jnp.sqrt(
        jnp.sum(jnp.square(mat_t.astype(jnp.float32)), axis=0, keepdims=True)
    )
    return mat_t, norms


def _gen_streaming_random(key, num_features, n_pad, n_items, dtype):
    # Chunked fill with buffer donation: generating a [250, 20M] matrix in
    # one call would materialize the RNG bit tensor next to the output
    # (2x peak); 2M-column blocks bound the transient to ~1 GB while the
    # donated buffer stays in place.
    chunk = min(n_pad, 2_000_000)
    buf = jnp.zeros((num_features, n_pad), dtype=dtype)
    starts = list(range(0, n_pad, chunk))
    keys = jax.random.split(key, len(starts))
    for i, start in enumerate(starts):
        # keep the block width static for one compiled fill: clamp the
        # last start back so the block fits (the overlap is re-randomized,
        # which is harmless for benchmark data)
        buf = _fill_normal_block(buf, keys[i], min(start, n_pad - chunk), chunk)
    return _mask_and_norms(buf, jnp.int32(n_items), n_pad)


@jax.jit
def _quantize_cols_t(mat_t):
    """Column-wise (= per item row in the feature-major layout) symmetric
    int8 quantization on device — same absmax/127 rule as the host path,
    so padding columns (all-zero) get scale 1.0 and codes 0. Returns both
    planes (codes + residual codes) and their per-column scales."""

    def requant(v):
        absmax = jnp.max(jnp.abs(v), axis=0, keepdims=True)
        s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        q = jnp.clip(jnp.round(v / s), -127, 127)
        return q, s

    q, s = requant(mat_t)
    q2, s2 = requant(mat_t - q * s)
    return q.astype(jnp.int8), s, q2.astype(jnp.int8), s2


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _gen_plain_random(key, n_items, num_features, dtype):
    mat = jax.random.normal(key, (n_items, num_features), dtype=dtype)
    norms = jnp.linalg.norm(mat.astype(jnp.float32), axis=1)
    return mat, norms


@jax.jit
def _scatter_query_rows(x_dev, rows, vals):
    return x_dev.at[rows].set(vals)


def update_query_rows(x_dev: jax.Array, rows: np.ndarray, values: np.ndarray) -> jax.Array:
    """Scatter-update rows of a staged query matrix (the incremental
    refresh for device-resident X — same idea as update_rows for Y).
    Row counts bucket to powers of two (padding repeats the last row) so
    jit retraces O(log n) scatter shapes, not one per dirty-batch size."""
    rows = np.asarray(rows, dtype=np.int32)
    values = np.ascontiguousarray(values, dtype=np.float32)
    m = len(rows)
    if m == 0:
        return x_dev
    bucket = 1 << (m - 1).bit_length()
    if bucket != m:
        pad = bucket - m
        rows = np.concatenate([rows, np.repeat(rows[-1:], pad)])
        values = np.concatenate([values, np.repeat(values[-1:], pad, axis=0)])
    return _scatter_query_rows(x_dev, jnp.asarray(rows), jnp.asarray(values))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _indexed_multi_xla(mat, norms, x_dev, idx_kb, k, cosine, download_dtype):
    q_kb = x_dev[idx_kb].astype(mat.dtype)  # [K, b, feat] gathered on device
    return _dot_topk_batch_multi(mat, norms, q_kb, k, cosine, download_dtype)


def submit_top_k_multi_indexed(
    uploaded,
    x_dev: jax.Array,
    indices: np.ndarray,
    k: int,
    cosine: bool = False,
    scan_batch: int = 256,
    nprobe: int | None = None,
) -> MultiTopNHandle:
    """submit_top_k_multi with the query VECTORS already device-resident:
    the host ships only int32 row indices into ``x_dev`` (4 B/query vs
    4*feat B — 50-200x less uplink on a wire-bound link) and the gather
    happens on device inside the same dispatch as the fused scans.

    This is the serving shape where the user-factor matrix X lives on
    device next to Y (e.g. refreshed by the same scatter-update path);
    /recommend then resolves the user id to a row index and never uploads
    a vector at all."""
    idx = np.atleast_1d(np.asarray(indices, dtype=np.int32))
    if isinstance(uploaded, IVFIndex):
        vals, ids = ivf_ops.top_k_device_indexed(
            uploaded, x_dev, idx, k, cosine=cosine, nprobe=nprobe
        )
        return _async_multi_handle(vals[None], ids[None], len(idx))
    idx_kb_np, n = _group_pad(idx, scan_batch)
    idx_kb = jnp.asarray(idx_kb_np)
    dl = _auto_download_dtype(uploaded)
    if isinstance(uploaded, StreamingItemMatrix):
        from oryx_tpu.ops.pallas_topn import top_k_streaming_device_multi_indexed

        vals, idxs = top_k_streaming_device_multi_indexed(
            uploaded, x_dev, idx_kb, k, cosine=cosine, download_dtype=dl
        )
    else:
        mat, norms = uploaded
        kk = max(1, min(int(k), mat.shape[0]))
        vals, idxs = _indexed_multi_xla(mat, norms, x_dev, idx_kb, kk, cosine, dl)
    return _async_multi_handle(vals, idxs, n)


def submit_top_k(
    uploaded, queries: np.ndarray, k: int, cosine: bool = False,
    nprobe: int | None = None,
) -> TopNHandle:
    """Enqueue a batched top-k without waiting: device compute and the
    device→host copy both run asynchronously. Keeping a window of
    handles in flight pipelines transfers behind compute. ``nprobe``
    overrides the IVF index's default probe count per call (the overload
    controller's reduced-probe rung); ignored for non-IVF handles."""
    if isinstance(uploaded, IVFIndex):
        vals, ids = ivf_ops.top_k_device(
            uploaded, np.atleast_2d(queries), k, cosine=cosine, nprobe=nprobe
        )
        vals.copy_to_host_async()
        ids.copy_to_host_async()
        return TopNHandle(vals, ids)
    dl = _auto_download_dtype(uploaded)
    if isinstance(uploaded, StreamingItemMatrix):
        vals, idxs = top_k_streaming_device(
            uploaded, queries, k, cosine=cosine, download_dtype=dl
        )
    else:
        mat, norms = uploaded
        kk = max(1, min(int(k), mat.shape[0]))
        q = jnp.asarray(np.atleast_2d(queries), dtype=mat.dtype)
        vals, idxs = _dot_topk_batch(mat, norms, q, kk, cosine, dl)
    vals.copy_to_host_async()
    idxs.copy_to_host_async()
    return TopNHandle(vals, idxs)
