"""Top-N scoring: batched matvec + top_k on device.

Replaces the reference's per-request thread-pool scan over LSH partitions
(ALSServingModel.topN / TopNConsumer.java, VectorMath.dot in the hot
loop): dot scores for ALL items are computed on the MXU and top-k
selected on device. ``upload`` picks the handle, and every handle kind
is scanned through ONE routine (``_submit``), which cuts the query rows
into groups of at most 256 and walks the kinds once:

- the plain ``(matrix, norms)`` pair: ``scores = Q @ Y.T`` +
  ``lax.top_k`` a group — the tests' reference and the CPU default;
- :class:`StreamingItemMatrix` (TPU, and int8 everywhere): the fused
  streaming kernel in :mod:`oryx_tpu.ops.pallas_topn`, which never
  materializes the [b, n] score matrix in HBM and can hold items in
  bfloat16 or int8 — 2-12x less HBM traffic at 1M+ items;
- :class:`ShardedItemMatrix`: that kernel on every shard of a mesh, the
  candidates merged across chips;
- :class:`IVFIndex`: the approximate tier (``ops/ivf.py``), which groups
  its queries itself.

``submit_top_k`` (query vectors) and ``submit_top_k_multi_indexed`` (rows
of a query matrix staged on the device) enqueue the device computation
and a non-blocking device→host copy, returning a :class:`TopNHandle`
whose ``result()`` materializes the answer. A pass is ONE dispatch and
ONE download: the row groups go into the jitted program as the NumPy
array they are, and float32 scores come back in one int32 array with
their ids (``pallas_topn.pack_hits``). Callers that keep several
requests in flight (the serving batcher) overlap device compute and
host<->device transfers instead of paying a full round-trip per request.
``top_k_scores`` / ``top_k_scores_batch`` are the blocking forms for
tests, tools and one-off probes.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from oryx_tpu.ops import ivf as ivf_ops
from oryx_tpu.ops.ivf import IVFIndex
from oryx_tpu.ops.pallas_topn import (
    MAX_GROUP_ROWS,
    StreamingItemMatrix,
    _is_int8,
    _quantize_residual,
    _quantize_rows,
    group_rows,
    note_feature_rows,
    pack_hits,
    scan_groups,
    split_features,
    split_hits,
    tail_rows,
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


def _dot_topk_batch(mat, norms, queries, k, cosine):
    """The plain pair's scan of one group: the [b, n] score block and
    XLA's ``top_k``."""
    scores = jnp.dot(
        queries, mat.T, preferred_element_type=jnp.float32, precision=_dot_precision(mat.dtype)
    )  # [b, n]
    if cosine:
        qn = jnp.linalg.norm(queries.astype(jnp.float32), axis=1, keepdims=True)
        scores = scores / jnp.maximum(norms[None, :] * qn, 1e-12)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _plain_topk_groups(mat, norms, x_dev, groups, k, cosine, download_dtype):
    """The plain pair's twin of the fused multi-scan: lax.map over query
    groups keeps peak memory at one [b, n] score block instead of
    [K*b, n]. ``groups`` is [K, b, feat] vectors or, with ``x_dev``,
    [K, b] rows of it, gathered on the device. Returns ``pack_hits`` of
    (vals, idxs), as the fused scan does."""
    q_kb = (groups if x_dev is None else x_dev[groups]).astype(mat.dtype)
    vals, idxs = jax.lax.map(lambda q: _dot_topk_batch(mat, norms, q, k, cosine), q_kb)
    return pack_hits(vals, idxs, download_dtype)


# -- mesh-sharded scan --------------------------------------------------------


@dataclass
class ShardedItemMatrix:
    """Item matrix row-sharded over a device mesh, each shard in the
    streaming kernel's layout: device s holds rows ``starts[s]`` to
    ``starts[s] + counts[s]`` feature-major, ``[k_feat, cols]`` with
    ``cols`` a BLOCK_N multiple, plus their norms (and, for int8, scales
    and the residual plane; for float32 with ``tail_rows``, the tail plane
    of the same split the streaming handle makes). The serving layout of
    a catalog past one chip's memory: 20M x 250 float32 is 20 GB, 5 GB a
    chip on a v5e host's four. One pass scans every shard with the single-device kernel and
    merges the ``[b, k]`` candidates across chips (``_sharded_scan_fn``).
    Rows split evenly (the first ``n % d`` shards hold one more), so no
    shard is empty once there is a row a device; the columns past a
    shard's count are padding, masked in the kernel by that count."""

    mat_t: jax.Array  # [k_feat(_pad), d * cols], columns sharded over 'data'
    norms: jax.Array  # [1, d * cols], sharded alike
    n_items: int
    mesh: object
    counts: tuple  # rows held by each shard; the last may grow into its padding
    starts: tuple  # global row of each shard's first column
    base: jax.Array  # [d] int32 = starts, one a shard
    valid: jax.Array  # [d] int32 = counts, one a shard
    scales: jax.Array | None = None  # [1, d * cols] per-row int8 dequant scale
    resid: jax.Array | None = None  # [k_feat_pad, d * cols] int8 residual plane
    resid_scales: jax.Array | None = None  # [1, d * cols]
    features: int | None = None  # true feature count where the stored rows are not it
    tail: jax.Array | None = None  # [1 | 2 | 4, d * cols] float32: pallas_topn.tail_rows

    @property
    def cols(self) -> int:
        return self.mat_t.shape[1] // len(self.counts)


def _shard_counts(n: int, d: int) -> tuple[tuple, tuple]:
    counts = tuple(n // d + (1 if s < n % d else 0) for s in range(d))
    starts = tuple(int(x) for x in np.cumsum((0,) + counts[:-1]))
    return counts, starts


def _per_shard(mesh, values) -> jax.Array:
    """[d] int32, element s on device s."""
    from oryx_tpu.parallel.mesh import shard_rows

    return jax.device_put(np.asarray(values, dtype=np.int32), shard_rows(mesh))


def _put_after(before: list, planes: list, device) -> list:
    """Put one shard's host planes on its device once the slice before it
    has left the host: one slice is on its way while the next is laid
    out, so the host holds two slices (and a staging copy) beside the
    matrix however many devices there are."""
    jax.block_until_ready(before)
    return [jax.device_put(p, device) for p in planes]


def upload_sharded(matrix: np.ndarray, mesh, dtype=None) -> ShardedItemMatrix:
    """Shard a packed [n, k] item matrix row-wise over `mesh`'s devices.
    Each device's slice is cut from the host matrix, laid out for the
    kernel and put on that device alone: no device ever holds the whole
    matrix, and the host holds two slices beside the matrix (one on its
    way to its device while the next is laid out), not a second matrix.
    ``dtype=int8`` row-quantizes each slice exactly like the streaming
    handle (codes, residual codes, one scale a row each)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from oryx_tpu.common.metrics import registry as metrics
    from oryx_tpu.ops.pallas_topn import (
        _INT8_FEAT_MULTIPLE,
        BLOCK_N,
        _ceil_to,
        feature_major,
        row_norms,
    )
    from oryx_tpu.parallel.mesh import DATA_AXIS

    n, k_feat = matrix.shape
    devices = list(mesh.devices.flat)
    d = len(devices)
    counts, starts = _shard_counts(n, d)
    cols = max(BLOCK_N, _ceil_to(counts[0], BLOCK_N))
    quantized = _is_int8(dtype)
    kf = _ceil_to(k_feat, _INT8_FEAT_MULTIPLE) if quantized else k_feat
    tailed = not quantized and tail_rows(k_feat, dtype or jnp.float32) > 0

    def a_row(values: np.ndarray, fill: float) -> np.ndarray:
        out = np.full((1, cols), fill, dtype=np.float32)
        out[0, : values.shape[0]] = values
        return out

    def planes_of(lo: int, cnt: int) -> dict[str, np.ndarray]:
        rows = np.asarray(matrix[lo : lo + cnt], dtype=np.float32)
        parts = {"norms": a_row(row_norms(rows), 0.0)}
        if quantized:
            q, s = _quantize_rows(rows)
            q2, s2 = _quantize_residual(rows, q, s)
            parts["mat_t"] = feature_major(q, cols, np.int8, kf)
            parts["resid"] = feature_major(q2, cols, np.int8, kf)
            parts["scales"] = a_row(s, 1.0)  # pad: scale 1.0
            parts["resid_scales"] = a_row(s2, 1.0)
        elif tailed:
            parts["mat_t"], parts["tail"] = split_features(rows, cols)
        else:
            parts["mat_t"] = feature_major(rows, cols, np.dtype(dtype or jnp.float32))
        return parts

    names = ("mat_t", "norms") + (("scales", "resid", "resid_scales") if quantized else ())
    names += ("tail",) if tailed else ()
    on_device: dict[str, list] = {name: [] for name in names}
    on_its_way: list = []
    for dev, lo, cnt in zip(devices, starts, counts):
        parts = planes_of(lo, cnt)
        on_its_way = _put_after(on_its_way, [parts[name] for name in names], dev)
        for name, put in zip(names, on_its_way):
            on_device[name].append(put)
    jax.block_until_ready(on_its_way)

    def assemble(name: str) -> jax.Array:
        shards = on_device[name]
        return jax.make_array_from_single_device_arrays(
            (shards[0].shape[0], d * cols), NamedSharding(mesh, P(None, DATA_AXIS)), shards
        )

    up = ShardedItemMatrix(
        n_items=n, mesh=mesh, counts=counts, starts=starts,
        base=_per_shard(mesh, starts), valid=_per_shard(mesh, counts),
        features=k_feat if kf != k_feat or tailed else None,
        **{name: assemble(name) for name in names},
    )
    note_feature_rows(up)
    metrics.gauge("serving.scan.shards").set(sum(1 for c in counts if c))
    metrics.gauge("serving.scan.shard.rows-max").set(max(counts))
    metrics.gauge("serving.scan.shard.rows-min").set(min(counts))
    log.info("sharded item matrix, %d items, shards: %s", n, sharded_layout(up))
    return up


def sharded_layout(up: ShardedItemMatrix) -> str:
    """'dev0:(rows, features) dev1:(rows, features) ...': the rows each
    device's slice holds, by the array's own addressable shards; the
    features are the logical count, however many planes store them."""
    feats = up.features if up.features is not None else up.mat_t.shape[0]
    return " ".join(
        f"dev{s.device.id}:({up.counts[(s.index[1].start or 0) // up.cols]}, {feats})"
        for s in up.mat_t.addressable_shards
    )


@functools.lru_cache(maxsize=None)
def _sharded_scan_fn(
    mesh, k: int, cosine: bool, quantized: bool, indexed: bool, download_dtype,
    interpret: bool | None = None, tailed: bool = False,
):
    """The mesh's one scan program: under ``shard_map`` every device runs
    the single-device dispatch of its backend on its own ``[k_feat, cols]``
    slice (the Pallas kernel ``oryx_topn_scan`` on the TPU, its XLA twin
    elsewhere; ``Precision.HIGHEST`` for float32 as there) for each group
    of replicated query rows, adds its first row's global number to the
    ids, and the ``[groups, b, k]`` candidates of all devices are gathered
    and merged by one ``top_k`` over ``d * k``, on every device alike. No
    ``[b, n]`` score matrix exists anywhere and nothing of the item matrix
    crosses a chip. Candidates are gathered in shard order and ``top_k`` is
    stable, so equal scores resolve to the lower global row, as they do on
    one chip. Keyed by the mesh itself (``Mesh`` hashes by value).
    ``interpret`` as in ``top_k_streaming_device``: None picks per backend,
    False compiles the kernel (the compile rehearsal for a described mesh).
    The third operand is the planes beside ``mat_t`` and ``norms``:
    ``(scales, resid, resid_scales)`` where ``quantized``, ``(tail,)``
    where ``tailed`` (the float32 split, ``pallas_topn.tail_rows``), else
    ``()``."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from oryx_tpu.ops import pallas_topn as pt
    from oryx_tpu.parallel.mesh import DATA_AXIS

    use_xla = pt._use_xla_scan(interpret)

    def local(mat_t, norms, beside, base, valid, groups, x_dev):
        scales, resid, resid_scales = beside if quantized else (None, None, None)
        tail = beside[0] if tailed else None
        capacity = mat_t.shape[1]

        def one(g):
            q = (x_dev[g] if indexed else g).astype(jnp.float32)
            shared = dict(k=k, n_items=capacity, cosine=cosine, n_valid=valid, tail=tail)
            if use_xla:
                return pt._xla_streaming_topk_impl(
                    mat_t, norms, scales, resid, resid_scales, q, **shared
                )
            return pt._streaming_topk_impl(
                mat_t, norms, scales, resid, resid_scales, q, interpret=bool(interpret), **shared
            )

        vals, idxs = jax.lax.map(one, groups)  # [groups, b, k], local ids
        idxs = idxs + base[0]
        v_all = jax.lax.all_gather(vals, DATA_AXIS, axis=2, tiled=True)
        i_all = jax.lax.all_gather(idxs, DATA_AXIS, axis=2, tiled=True)
        vm, pos = jax.lax.top_k(v_all, k)
        im = jnp.take_along_axis(i_all, pos, axis=2)
        if download_dtype is not None:
            vm = vm.astype(download_dtype)
        return vm, im

    cols_spec, shard_spec = P(None, DATA_AXIS), P(DATA_AXIS)
    in_specs = (
        cols_spec, cols_spec,
        (cols_spec,) * (3 if quantized else 1 if tailed else 0),
        shard_spec, shard_spec, P(), P() if indexed else (),
    )
    # after the all_gather every device computes the same merge; the
    # replication checker cannot see that through top_k
    return jax.jit(
        shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=(P(), P()), check_vma=False)
    )


@functools.lru_cache(maxsize=None)
def _packed(scan):
    """``scan`` (a jitted program that returns float32 scores and their
    ids) with ``pack_hits`` jitted around it: still one program and one
    dispatch, whose one output is the array a pass downloads."""
    def packed_scan(*operands):
        return pack_hits(*scan(*operands))

    return jax.jit(packed_scan)


def _submit_sharded(up: ShardedItemMatrix, groups: np.ndarray, k: int, cosine: bool, x_dev=None):
    """Enqueue one sharded pass for ``groups`` ([K, b, feat] query rows,
    or [K, b] int32 rows of ``x_dev``; NumPy, which the program's
    replicated in-spec places on every device inside the call); returns
    ``pack_hits`` of the device (vals, idxs) [K, b, k], replicated."""
    k = max(1, min(int(k), up.n_items))
    quantized = up.scales is not None
    download = _auto_download_dtype(up)
    fn = _sharded_scan_fn(
        up.mesh, k, bool(cosine), quantized, x_dev is not None, download,
        tailed=up.tail is not None,
    )
    if download is None:
        fn = _packed(fn)
    return fn(
        up.mat_t, up.norms,
        (up.scales, up.resid, up.resid_scales) if quantized
        else () if up.tail is None else (up.tail,),
        up.base, up.valid, groups, x_dev if x_dev is not None else (),
    )


# -- incremental updates ------------------------------------------------------


# No donation: in-flight top-k requests may still hold the previous
# handle, and donating would delete their buffers mid-request. The
# device-side copy this costs is HBM-internal (no host transfer — the
# thing incremental refresh exists to avoid) and transient.
@jax.jit
def _scatter_rows_t(mat_t, norms, tail, rows, vals, tail_vals, new_norms):
    """Feature-major scatter: mat_t[:, rows] <- vals.T, norms[0, rows] <- n,
    and tail[:, rows] <- tail_vals.T where the handle has a tail plane."""
    mat_t = mat_t.at[:, rows].set(vals.T.astype(mat_t.dtype))
    norms = norms.at[0, rows].set(new_norms)
    if tail is not None:
        tail = tail.at[:, rows].set(tail_vals.T)
    return mat_t, norms, tail


def _tail_values(values: np.ndarray, k_main: int, tail) -> np.ndarray | None:
    """[m, t] float32 for a row update of the ``tail`` plane: the features
    past the main plane's rows, zero-padded to the plane's height (3
    features ride in 4 rows); None where the handle has no tail."""
    if tail is None:
        return None
    return np.pad(values[:, k_main:], [(0, 0), (0, tail.shape[0] - (values.shape[1] - k_main))])


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


@functools.lru_cache(maxsize=None)
def _sharded_scatter_fn(mesh, n: int):
    """Row update of a sharded matrix: the touched rows' values travel to
    every device, each writes the ones whose column lies in its own slice
    (the rest fall outside and are dropped), for the handle's ``n`` planes.
    Like the single-device scatters it donates nothing: a pass in flight
    may hold the old slices."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from oryx_tpu.parallel.mesh import DATA_AXIS

    def local(planes, cols, vals):
        width = planes[0].shape[1]
        at = cols - jax.lax.axis_index(DATA_AXIS) * width
        at = jnp.where((at >= 0) & (at < width), at, width)  # outside: dropped
        return tuple(
            p.at[:, at].set(v.T.astype(p.dtype), mode="drop") for p, v in zip(planes, vals)
        )

    spec = (P(None, DATA_AXIS),) * n
    return jax.jit(
        shard_map(
            local, mesh=mesh, in_specs=(spec, P(), (P(),) * n),
            out_specs=spec, check_vma=False,
        )
    )


def _update_rows_sharded(up: ShardedItemMatrix, rows, values, new_norms, n_items):
    """``update_rows`` for the sharded layout: each row lands on the shard
    that holds it; rows past the catalog append into the last shard's
    padding (``capacity``)."""
    from oryx_tpu.parallel.mesh import replicated

    count = up.n_items if n_items is None else int(n_items)
    starts = np.asarray(up.starts)
    shard = np.searchsorted(starts, rows, side="right") - 1
    cols = (shard * up.cols + (rows - starts[shard])).astype(np.int32)
    k_main = up.mat_t.shape[0]
    if up.scales is not None:
        q, s = _quantize_rows(values)
        q2, s2 = _quantize_residual(values, q, s)
        pad = [(0, 0), (0, k_main - q.shape[1])]  # int8 sublane padding
        parts = {
            "mat_t": (up.mat_t, np.pad(q, pad)), "norms": (up.norms, new_norms[:, None]),
            "scales": (up.scales, s[:, None]), "resid": (up.resid, np.pad(q2, pad)),
            "resid_scales": (up.resid_scales, s2[:, None]),
        }
    else:
        parts = {"mat_t": (up.mat_t, values[:, :k_main]), "norms": (up.norms, new_norms[:, None])}
        if up.tail is not None:
            parts["tail"] = (up.tail, _tail_values(values, k_main, up.tail))
    planes, vals = zip(*parts.values())
    out = _sharded_scatter_fn(up.mesh, len(planes))(
        planes, *jax.device_put((cols, vals), replicated(up.mesh))
    )
    counts = up.counts[:-1] + (count - up.starts[-1],)
    grown = dict(zip(parts, out))
    return dataclasses.replace(
        up, n_items=count, counts=counts, valid=_per_shard(up.mesh, counts), **grown
    )


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
    if isinstance(uploaded, ShardedItemMatrix):
        return uploaded.starts[-1] + uploaded.cols  # the last shard's padding
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
    if isinstance(uploaded, ShardedItemMatrix):
        return _update_rows_sharded(uploaded, rows, values, new_norms, n_items)
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
        k_main = uploaded.mat_t.shape[0]  # every feature, or the main plane's
        mat_t, norms, tail = _scatter_rows_t(
            uploaded.mat_t, uploaded.norms, uploaded.tail, rows,
            values[:, :k_main], _tail_values(values, k_main, uploaded.tail), new_norms,
        )
        return dataclasses.replace(uploaded, mat_t=mat_t, norms=norms, tail=tail, n_items=count)
    mat, norms = uploaded
    return _scatter_rows(mat, norms, rows, values, new_norms)


@dataclass
class TopNHandle:
    """In-flight async top-k request; ``result()`` blocks and returns
    (indices [n, k], scores [n, k]) as numpy arrays for the n query rows
    submitted, whatever groups and padding the device was given."""

    _vals: jax.Array  # [..., k] scores; or, packed, [..., 2k] int32 (``pack_hits``)
    _idxs: jax.Array | None  # [..., k] ids; None where ``_vals`` holds them too
    _n: int

    @property
    def packed(self) -> bool:
        """Whether the pass comes back as one array: one fetch."""
        return self._idxs is None

    def result(self) -> tuple[np.ndarray, np.ndarray]:
        if self.packed:
            hits = np.asarray(self._vals)
            vals, idxs = split_hits(hits.reshape(-1, hits.shape[-1])[: self._n])
            return idxs, vals
        k = self._vals.shape[-1]
        idxs = np.asarray(self._idxs).reshape(-1, k)[: self._n]
        # scores may travel as bf16 (download_dtype); callers always see f32
        vals = np.asarray(self._vals).astype(np.float32, copy=False).reshape(-1, k)[: self._n]
        return idxs, vals


def _auto_download_dtype(uploaded) -> object | None:
    """Scores of a bf16 item matrix carry ~bf16 information even though
    selection accumulates in f32 — shipping them back over a result-byte-
    bound link as bf16 cuts the per-hit payload from 8 B to 6 B without
    changing the on-device ranking. f32 matrices keep f32 results."""
    layouts = (StreamingItemMatrix, ShardedItemMatrix)
    mat = uploaded.mat_t if isinstance(uploaded, layouts) else uploaded[0]
    # int8 scores carry ~0.4% quantization error already — bf16 wire dtype
    # loses nothing that selection kept
    return jnp.bfloat16 if mat.dtype in (jnp.bfloat16, jnp.int8) else None


def _submit(
    uploaded, rows: np.ndarray, k: int, cosine: bool, x_dev=None,
    scan_batch: int = MAX_GROUP_ROWS, nprobe: int | None = None, wire_dtype: bool = True,
) -> TopNHandle:
    """Enqueue the top-k of ``rows`` ([n, feat] float32 query vectors or,
    with ``x_dev``, [n] int32 rows of that staged query matrix) against
    any handle kind, and the device→host copy behind it, without
    blocking. Every kind but IVF (whose program groups its queries
    itself, and alone knows ``nprobe``) runs ceil(n / scan_batch) scans
    of the whole matrix inside ONE dispatch (lax.map over zero-padded
    groups), so per-dispatch host work and the device round-trip are paid
    once; ``scan_batch`` bounds a scan's VMEM ([scan_batch, SCORE_TILE]
    f32 scores). The groups go in as NumPy (the jitted call transfers
    them) and a program whose scores leave the device as float32 hands
    back one array (``pack_hits``): one copy, one fetch; the IVF index
    and the bfloat16 wire keep their pair. ``wire_dtype=False`` keeps
    float32 scores where a served pass would download bfloat16
    (``_auto_download_dtype``; the sharded layout's one program downloads
    what it serves either way)."""
    if isinstance(uploaded, IVFIndex):
        if x_dev is None:
            hits = ivf_ops.top_k_device(uploaded, rows, k, cosine=cosine, nprobe=nprobe)
        else:
            hits = ivf_ops.top_k_device_indexed(
                uploaded, x_dev, rows, k, cosine=cosine, nprobe=nprobe
            )
    else:
        groups = group_rows(rows, scan_batch)
        download = _auto_download_dtype(uploaded) if wire_dtype else None
        if isinstance(uploaded, ShardedItemMatrix):
            hits = _submit_sharded(uploaded, groups, k, cosine, x_dev=x_dev)
        elif isinstance(uploaded, StreamingItemMatrix):
            hits = scan_groups(
                uploaded, groups, k, cosine=cosine, download_dtype=download, x_dev=x_dev
            )
        else:
            mat, norms = uploaded
            hits = _plain_topk_groups(
                mat, norms, x_dev, groups, max(1, min(int(k), mat.shape[0])), cosine, download
            )
    vals, idxs = hits if isinstance(hits, tuple) else (hits, None)
    vals.copy_to_host_async()
    if idxs is not None:
        idxs.copy_to_host_async()
    return TopNHandle(vals, idxs, rows.shape[0])


def submit_top_k(
    uploaded, queries: np.ndarray, k: int, cosine: bool = False, nprobe: int | None = None,
) -> TopNHandle:
    """Enqueue a batched top-k of [n, feat] query vectors without
    waiting. Keeping a window of handles in flight pipelines transfers
    behind compute. ``nprobe`` overrides the IVF index's default probe
    count per call (the overload controller's reduced-probe rung);
    ignored for non-IVF handles, which have no probe concept."""
    q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
    return _submit(uploaded, q, k, cosine, nprobe=nprobe)


def submit_top_k_multi_indexed(
    uploaded,
    x_dev: jax.Array,
    indices: np.ndarray,
    k: int,
    cosine: bool = False,
    scan_batch: int = MAX_GROUP_ROWS,
    nprobe: int | None = None,
) -> TopNHandle:
    """submit_top_k with the query VECTORS already device-resident: the
    host ships only int32 row indices into ``x_dev`` (4 B/query vs
    4*feat B — 50-200x less uplink on a wire-bound link) and the gather
    happens on device inside the same dispatch as the fused scans.

    This is the serving shape where the user-factor matrix X lives on
    device next to Y (e.g. refreshed by the same scatter-update path);
    /recommend then resolves the user id to a row index and never uploads
    a vector at all."""
    idx = np.atleast_1d(np.asarray(indices, dtype=np.int32))
    return _submit(uploaded, idx, k, cosine, x_dev=x_dev, scan_batch=scan_batch, nprobe=nprobe)


def top_k_scores_batch(uploaded, queries: np.ndarray, k: int, cosine: bool = False):
    """(indices [b, k], scores [b, k]) for [b, feat] query vectors: one
    blocking pass with float32 scores (tests and tools; the serving path
    submits through the batcher)."""
    q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
    return _submit(uploaded, q, k, cosine, wire_dtype=False).result()


def top_k_scores(uploaded, query: np.ndarray, k: int, cosine: bool = False):
    """(indices, scores) of the k best items for one query vector."""
    idx, vals = top_k_scores_batch(uploaded, query, k, cosine=cosine)
    return idx[0], vals[0]


# rows of a staged query matrix travel in chunks of this many bytes: what a
# caller that reads them from a store holds on the host at a time
QUERY_CHUNK_BYTES = 256 << 20


def query_chunk_rows(features: int) -> int:
    """Rows of ``features`` float32 values in one staging chunk."""
    return max(1, QUERY_CHUNK_BYTES // (4 * max(1, features)))


@functools.partial(jax.jit, donate_argnums=0)
def _write_query_rows(buf, chunk, start):
    return jax.lax.dynamic_update_slice(buf, chunk, (start, 0))


def _put_query_rows(buf, chunk: np.ndarray, start: int, where):
    """One chunk on the device and written, DONE before the caller reads
    its next: a device that is busy (an item matrix still on its way up)
    would otherwise let every chunk queue beside the buffer, a second
    copy of the matrix in all but name."""
    buf = _write_query_rows(buf, jax.device_put(chunk, where), np.int32(start))
    buf.block_until_ready()
    return buf


def stage_queries(chunks, capacity: int, features: int, mesh=None) -> jax.Array:
    """A [capacity, features] float32 query matrix on the device, filled
    from ``chunks`` (an iterable of [rows, features] arrays) in order from
    row 0; the rows past the last chunk stay zero. The buffer is made at
    its final size and each chunk is written into it in place (donated:
    nothing else holds it yet) before the next is read, so neither the
    host nor the device ever holds more than one chunk beside the matrix,
    and headroom rows are never sent.
    With a ``mesh`` (the sharded item layout) a copy on each of its
    devices, so every shard gathers its rows itself."""
    where = None
    if mesh is not None:
        from oryx_tpu.parallel.mesh import replicated

        where = replicated(mesh)
    buf = jnp.zeros((capacity, features), jnp.float32, device=where)
    at = 0
    for chunk in chunks:
        chunk = np.asarray(chunk, np.float32)
        if at + len(chunk) > capacity:
            raise ValueError(f"{at + len(chunk)} query rows exceed the capacity {capacity}")
        if len(chunk):
            buf = _put_query_rows(buf, chunk, at, where)
            at += len(chunk)
    return buf


def upload_queries(queries: np.ndarray, mesh=None) -> jax.Array:
    """Stage a whole [m, feat] query-vector matrix on device (float32),
    for index-submitted scans: :func:`stage_queries` of its own rows."""
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    step = query_chunk_rows(queries.shape[1])
    return stage_queries(
        (queries[lo : lo + step] for lo in range(0, len(queries), step)),
        len(queries), queries.shape[1], mesh=mesh,
    )


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
        t = tail_rows(num_features, dtype)
        if not t:
            return StreamingItemMatrix(mat_t=mat_t, norms=norms, n_items=n_items)
        # the same values as one plane would hold, cut where upload cuts them
        mat_t, tail = _split_planes(mat_t, t)
        return StreamingItemMatrix(
            mat_t=mat_t, norms=norms, n_items=n_items, features=num_features, tail=tail
        )
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


@functools.partial(jax.jit, static_argnums=1)
def _split_planes(mat_t, t):
    """(main plane, tail plane of ``t`` rows) of a whole [k, n] float32
    plane, as ``pallas_topn.split_features`` cuts a host matrix."""
    k_main = mat_t.shape[0] - mat_t.shape[0] % 8
    tail = mat_t[k_main:]
    return mat_t[:k_main], jnp.pad(tail, ((0, t - tail.shape[0]), (0, 0)))


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
