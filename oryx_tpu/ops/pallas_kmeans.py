"""Pallas TPU kernel: one fused Lloyd sweep for k-means.

The XLA formulation of a Lloyd iteration (ops/kmeans.py:_lloyd_run —
distance matmul, argmin, then segment_sum) walks the points array twice
and materializes the [n, k] distance matrix in HBM. This kernel fuses the
whole sweep into a single pass:

- grid over point blocks; centers stay resident in VMEM across steps;
- each step computes the block's squared distances on the MXU, takes the
  per-point argmin, and immediately reduces the block into partial
  centroid sums via a one-hot matmul ``onehot(assign).T @ points`` (MXU
  again) plus per-cluster counts and the block's cost;
- partials accumulate into the kernel outputs across sequential grid
  steps (TPU grids execute in order on a core), so HBM sees the points
  exactly once per sweep and only [k, d] + [k] + [1] results ever come
  back.

The reference delegates this loop to Spark MLlib's KMeans
(app/oryx-app-mllib/.../kmeans/KMeansUpdate.java:116-117), where each
iteration is a cluster-wide map-reduce; here an iteration is one kernel
launch. Used by train_kmeans on TPU; the XLA path remains for meshes
(auto-sharded) and non-TPU backends, and tests run this kernel under the
Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 1024

# fits_vmem plans 12 MiB of blocks; what Mosaic allocates for the sweep's
# temporaries ([B, kp] distances, one-hot, the HIGHEST-precision dots) comes
# on top, so the kernel states its scoped-VMEM limit instead of relying on
# the 16 MiB default. Grid steps accumulate into the outputs: in order.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=32 * 2**20
)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def fits_vmem(k: int, d: int, budget_bytes: int = 12 * 1024 * 1024) -> bool:
    """Whether one sweep's block working set (points block + centers/sums
    + distance and one-hot blocks, double-buffered) fits the VMEM budget.
    Lives here so the estimate tracks the kernel's actual shapes."""
    kp = max(8, _ceil_to(k, 8))
    working = 4 * 2 * (BLOCK_N * d + 2 * kp * d + 2 * BLOCK_N * kp + kp)
    return working <= budget_bytes


def _sweep_kernel(pts_ref, ctr_ref, sums_ref, counts_ref, cost_ref, *, n_items, k_real):
    i = pl.program_id(0)
    pts = pts_ref[:]  # [B, d]
    ctr = ctr_ref[:]  # [kp, d]
    b = pts.shape[0]
    kp = ctr.shape[0]
    precision = jax.lax.Precision.HIGHEST
    d2 = (
        jnp.sum(pts * pts, axis=1, keepdims=True)
        - 2.0 * jnp.dot(pts, ctr.T, preferred_element_type=jnp.float32, precision=precision)
        + jnp.sum(ctr * ctr, axis=1)[None, :]
    )  # [B, kp]
    col = jax.lax.broadcasted_iota(jnp.int32, (b, kp), 1)
    d2 = jnp.where(col < k_real, d2, jnp.float32(jnp.inf))  # padded centers lose
    row_global = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0) + i * b
    valid = row_global < n_items  # [B, 1] padding rows contribute nothing
    mind2 = jnp.min(d2, axis=1, keepdims=True)  # [B, 1]
    # first center attaining the min (stable tie-break, like jnp.argmin)
    amin = jnp.min(jnp.where(d2 == mind2, col, jnp.int32(2**31 - 1)), axis=1, keepdims=True)
    onehot = ((col == amin) & valid).astype(jnp.float32)  # [B, kp]
    psums = jnp.dot(onehot.T, pts, preferred_element_type=jnp.float32, precision=precision)
    pcounts = jnp.sum(onehot, axis=0)[None, :]  # [1, kp]
    pcost = jnp.sum(jnp.where(valid, jnp.maximum(mind2, 0.0), 0.0))

    # Mosaic can't store a bare scalar into VMEM ("Cannot store scalars to
    # VMEM" on hardware; the interpreter accepts it) — keep the cost as a
    # (1, 1) tile end to end.
    pcost_tile = jnp.reshape(pcost, (1, 1))

    @pl.when(i == 0)
    def _():
        sums_ref[:] = psums
        counts_ref[:] = pcounts
        cost_ref[:, :] = pcost_tile

    @pl.when(i > 0)
    def _():
        sums_ref[:] += psums
        counts_ref[:] += pcounts
        cost_ref[:, :] += pcost_tile


@functools.partial(jax.jit, static_argnames=("n_items", "k_real", "interpret"))
def _sweep(points, centers, *, n_items, k_real, interpret):
    return _sweep_impl(points, centers, n_items=n_items, k_real=k_real, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("iterations", "n_items", "k_real", "interpret")
)
def _lloyd_fused(points, centers0, *, iterations, n_items, k_real, interpret):
    """All Lloyd iterations in ONE dispatch: lax.fori_loop over the fused
    sweep kernel, centers updated on device between sweeps, so the host
    dispatches once per training run instead of once per iteration."""

    def body(_, ctr):
        sums, counts, _cost = _sweep_impl(
            points, ctr, n_items=n_items, k_real=k_real, interpret=interpret
        )
        return jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], ctr
        )

    ctr = jax.lax.fori_loop(0, iterations, body, centers0)
    sums, counts, cost = _sweep_impl(
        points, ctr, n_items=n_items, k_real=k_real, interpret=interpret
    )
    return ctr, counts, cost


def _sweep_impl(points, centers, *, n_items, k_real, interpret):
    """One fused assignment+reduction pass. points [n_pad, d] (rows beyond
    n_items are padding), centers [kp, d] (rows beyond k_real are padding).
    Returns (sums [kp, d], counts [kp], cost)."""
    n_pad, d = points.shape
    kp = centers.shape[0]
    grid = n_pad // BLOCK_N
    kernel = functools.partial(_sweep_kernel, n_items=n_items, k_real=k_real)
    common = {} if interpret else dict(memory_space=pltpu.VMEM)
    sums, counts, cost = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((BLOCK_N, d), lambda i: (i, 0), **common),
            pl.BlockSpec((kp, d), lambda i: (0, 0), **common),
        ],
        out_specs=[
            pl.BlockSpec((kp, d), lambda i: (0, 0), **common),
            pl.BlockSpec((1, kp), lambda i: (0, 0), **common),
            pl.BlockSpec((1, 1), lambda i: (0, 0), **common),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(points, centers)
    return sums, counts[0], cost[0, 0]


@functools.partial(
    jax.jit,
    static_argnames=("iterations", "batch", "n_items", "k_real", "interpret"),
)
def _minibatch_fused(
    points, centers0, key, *, iterations, batch, n_items, k_real, interpret
):
    """Mini-batch k-means (Sculley 2010) with every pass through the fused
    sweep kernel: each iteration gathers a random `batch`-point sample,
    runs ONE sweep over it (assignment + per-center sums/counts in a
    single kernel), and moves each touched center toward the batch mean
    with learning rate 1/v_c (v_c = cumulative assigned count). The
    whole schedule is one dispatch; a final full-data sweep yields the
    reported counts/cost."""
    bpad = max(BLOCK_N, _ceil_to(batch, BLOCK_N))
    kp = centers0.shape[0]

    def body(_, carry):
        ctr, v, key = carry
        key, ks = jax.random.split(key)
        # gather bpad rows, of which the sweep counts only the first
        # `batch` (rows past n_items-bounded indices never occur; rows
        # past `batch` are masked off by the kernel's n_items guard)
        idx = jax.random.randint(ks, (bpad,), 0, n_items)
        xb = points[idx]
        sums, counts, _ = _sweep_impl(
            xb, ctr, n_items=batch, k_real=k_real, interpret=interpret
        )
        v = v + counts
        ctr = ctr + (sums - counts[:, None] * ctr) / jnp.maximum(v, 1.0)[:, None]
        return ctr, v, key

    ctr, _, _ = jax.lax.fori_loop(
        0, iterations, body, (centers0, jnp.zeros(kp, jnp.float32), key)
    )
    sums, counts, cost = _sweep_impl(
        points, ctr, n_items=n_items, k_real=k_real, interpret=interpret
    )
    return ctr, counts, cost


def minibatch_lloyd_pallas(
    points,
    centers0: np.ndarray,
    iterations: int,
    batch: int,
    key,
    interpret: bool | None = None,
    n_items: int | None = None,
) -> tuple[np.ndarray, np.ndarray, float]:
    """Mini-batch counterpart of lloyd_pallas: same (centers, counts, cost)
    contract, but iterations touch `batch` sampled points each instead of
    all n — steady-state cost scales with the batch size. `key` is a JAX
    PRNG key driving the per-iteration samples."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k = centers0.shape[0]
    kp = max(8, _ceil_to(k, 8))
    if isinstance(points, jax.Array):
        if n_items is None:
            raise ValueError("n_items is required for pre-uploaded points")
        pts_dev = points
        n, d = n_items, points.shape[1]
    else:
        n = np.asarray(points).shape[0]
        pts_dev = jnp.asarray(pad_to_block(np.asarray(points, dtype=np.float32)))
        d = pts_dev.shape[1]
    ctr = np.zeros((kp, d), np.float32)
    ctr[:k] = np.asarray(centers0, np.float32)
    ctr_dev, counts, cost = _minibatch_fused(
        pts_dev,
        jnp.asarray(ctr),
        key,
        iterations=iterations,
        batch=min(batch, n),
        n_items=n,
        k_real=k,
        interpret=interpret,
    )
    return np.asarray(ctr_dev[:k]), np.asarray(counts[:k]), float(cost)


def pad_to_block(points: np.ndarray) -> np.ndarray:
    """Points padded with zero rows to a BLOCK_N multiple (the kernel's
    grid granule)."""
    n, d = points.shape
    n_pad = max(BLOCK_N, _ceil_to(n, BLOCK_N))
    if n_pad == n:
        return points
    return np.concatenate([points, np.zeros((n_pad - n, d), np.float32)])


def lloyd_pallas(
    points,
    centers0: np.ndarray,
    iterations: int,
    interpret: bool | None = None,
    n_items: int | None = None,
) -> tuple[np.ndarray, np.ndarray, float]:
    """Lloyd iterations via the fused sweep; returns (centers, counts, cost)
    with the same semantics as ops.kmeans._lloyd_run (final counts/cost
    measured against the final centers). ``points`` may be a device array
    already padded to a BLOCK_N multiple (pass ``n_items`` = real row
    count) — that lets callers start the upload before host-side init."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k = centers0.shape[0]
    kp = max(8, _ceil_to(k, 8))
    if isinstance(points, jax.Array):
        if n_items is None:
            raise ValueError("n_items is required for pre-uploaded points")
        if points.shape[0] % BLOCK_N:
            raise ValueError("pre-uploaded points must be padded to BLOCK_N")
        if points.dtype != jnp.float32:
            raise ValueError("pre-uploaded points must be float32")
        n, d = n_items, points.shape[1]
        pts_dev = points
    else:
        n = np.asarray(points).shape[0]
        points = pad_to_block(np.asarray(points, dtype=np.float32))
        d = points.shape[1]
        pts_dev = jnp.asarray(points)
    ctr = np.zeros((kp, d), np.float32)
    ctr[:k] = centers0
    ctr_dev = jnp.asarray(ctr)
    ctr_dev, counts, cost = _lloyd_fused(
        pts_dev, ctr_dev, iterations=iterations, n_items=n, k_real=k, interpret=interpret
    )
    return (
        np.asarray(ctr_dev[:k]),
        np.asarray(counts[:k]),
        float(cost),
    )
