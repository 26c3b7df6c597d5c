"""ALS matrix factorization on TPU.

The TPU-native replacement for Spark MLlib's ALS (the hot loop of the
reference's ALSUpdate, app/oryx-app-mllib/.../als/ALSUpdate.java:116-124):
alternating normal-equation sweeps solved as batched k x k systems on
device.

Design (TPU-first, not a port):
- Ratings arrive as COO (user_idx, item_idx, value). Host-side they are
  grouped per-row and packed into **degree buckets**: rows whose rating
  count rounds up to the same power-of-two width D share a padded
  [N_b, D] rectangle of neighbor indices + values + mask. Fixed shapes
  mean XLA compiles one program per (bucket width, chunk) pair —
  logarithmically many — while a power-law degree distribution no longer
  forces every row to the max degree (a single 10k-rating user used to
  inflate the gather workspace for all rows; now it sits alone in a wide
  bucket and everyone else stays narrow).
- One half-sweep solves all users at once:
    implicit (Hu/Koren/Volinsky, MLlib semantics):
        c_ui = 1 + alpha*|r|, p_ui = 1 if r > 0 else 0
        A_u = YtY + sum_i (c-1) y_i y_i^T + lambda*I ;  b_u = sum_i c*p*y_i
    explicit (ALS-WR weighted-lambda):
        A_u = sum_i y_i y_i^T + lambda*n_u*I        ;  b_u = sum_i r y_i
  built with gathers + einsum (MXU work) and solved with batched
  jnp.linalg.solve. Rows are processed in chunks sized so the [C, D, k]
  gather workspace stays under a fixed HBM budget regardless of D.
- Replicated mode (default): neighbor buckets are sharded over rows on
  the mesh's 'data' axis; factor matrices live replicated, so YtY needs
  no collective and the per-row gather is local. XLA inserts the
  all-gather of the updated factors between half-sweeps.
- Sharded-factor mode (``shard_factors=True``): X and Y live sharded
  over the mesh (rows never replicated) so factorizations larger than
  one device's HBM fit a slice — the capability MLlib gets from block-
  partitioning (ALSUpdate.java:116-124, SURVEY.md §5). Each half-sweep
  runs under ``shard_map``: the implicit-feedback Gramian YtY is a
  ``psum`` of local Gramians, and the neighbor gather becomes a **ring
  exchange** — at ring step s each device holds item-factor shard
  (d+s) mod S (moved with ``ppermute`` over ICI) and fills the slots of
  its local [C, D, k] workspace whose item lives in that shard. After S
  steps the workspace is complete and the normal-equation solve is
  purely local. Factors are stored in bucket-permuted layout on device;
  the host keeps the permutation and restores natural order on export.
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oryx_tpu.parallel.mesh import DATA_AXIS, pad_to_multiple, shard_layout

log = logging.getLogger(__name__)


@dataclass
class NeighborBlock:
    """Padded per-row neighbor structure for one side of the factorization."""

    idx: np.ndarray  # [N, D] int32 indices into the other side's factors
    val: np.ndarray  # [N, D] float32 rating values (0 where padded)
    mask: np.ndarray  # [N, D] float32 1/0 validity

    @property
    def num_rows(self) -> int:
        return self.idx.shape[0]


def build_neighbor_block(
    row_idx: np.ndarray,
    col_idx: np.ndarray,
    values: np.ndarray,
    num_rows: int,
    pad_rows_to: int = 1,
) -> NeighborBlock:
    """Group COO entries by row and pad to [N, Dmax] rectangles.

    Retained for small problems and tests; ``train_als`` uses the
    degree-bucketed :func:`build_neighbor_buckets` (a max-degree
    rectangle explodes on power-law data — VERDICT r1 #2)."""
    order = np.argsort(row_idx, kind="stable")
    r, c, v = row_idx[order], col_idx[order], values[order]
    counts = np.bincount(r, minlength=num_rows)
    dmax = max(1, int(counts.max()) if counts.size else 1)
    n = pad_to_multiple(max(num_rows, 1), pad_rows_to)
    idx = np.zeros((n, dmax), dtype=np.int32)
    val = np.zeros((n, dmax), dtype=np.float32)
    mask = np.zeros((n, dmax), dtype=np.float32)
    # vectorized scatter: position of each entry within its row
    starts = np.concatenate([[0], np.cumsum(counts)])
    pos = np.arange(len(r)) - starts[r]
    idx[r, pos] = c
    val[r, pos] = v
    mask[r, pos] = 1.0
    return NeighborBlock(idx, val, mask)


# NeighborBucket and both packing implementations live in ops/packing.py
# (numpy + stdlib only, so forked packing workers never import jax);
# re-exported here for API compatibility.
from oryx_tpu.ops.packing import (  # noqa: E402  (re-export)
    NeighborBucket,
    PackingOptions,
    _pow2_at_least,
    build_neighbor_buckets_reference,
    pack_neighbor_buckets,
)

# wall seconds of the most recent train_als call (replicated path), split
# by phase ({"pack": s, "init": s, "iterate": s}: neighbor-bucket packing
# vs the rest of setup (factor init, device_put) vs the compiled sweep
# run); read by tools/train_benchmark.py. Overwritten per call, never
# merged.
last_phase_seconds: dict[str, float] = {}


def _pcast_varying(x):
    """Mark an array device-varying inside shard_map so scan carries that
    mix it with ppermute outputs have one type."""
    return jax.lax.pcast(x, (DATA_AXIS,), to="varying")


def _mask_from_deg(shape, deg):
    """[C, D] f32 validity mask from per-slot degrees: bucket entries
    occupy positions 0..deg-1, so the mask is a comparison against an
    iota — computed in-register on device instead of stored in HBM."""
    return (
        jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) < deg[..., None]
    ).astype(jnp.float32)


def build_neighbor_buckets(
    row_idx: np.ndarray,
    col_idx: np.ndarray,
    values: np.ndarray,
    num_rows: int,
    num_shards: int = 1,
    min_width: int = 8,
    workspace_elems: int = 1 << 27,
    features: int = 50,
    stable_shapes: bool = True,
    options: PackingOptions | None = None,
) -> list[NeighborBucket]:
    """Group COO entries by row into power-of-two degree buckets.

    Rows with no ratings appear in no bucket (their factors stay zero,
    matching the rectangle path where an all-masked row solves to the
    zero vector). Each bucket's chunk size is chosen so the [chunk, D, k]
    gather workspace stays under ``workspace_elems`` elements, and its
    slot count is padded (rows = -1) to a multiple of chunk*num_shards so
    every device runs the same number of full-width lax.map steps.

    ``stable_shapes`` (default) additionally rounds each bucket's slot
    count up to a power of two, so the (num_slots, width, chunk) shape
    signature takes log-many values as the dataset grows: consecutive
    generations of a growing factorization land on the same signature and
    reuse the compiled sweep instead of retracing. Pad slots are
    zero-degree and solve to the zero vector into the sacrificial row, so
    the padding is numerically free; the pow2 round-up also never more
    than doubles a bucket, same bound as the granule heuristic it
    replaces. Falls back to exact-granule padding when num_shards is not
    a power of two.

    Delegates to the sharded engine in :mod:`oryx_tpu.ops.packing`
    (``options`` selects worker count / chunking / shm budget), whose
    layout is bit-identical to :func:`build_neighbor_buckets_reference`
    for every option value — callers and the compile cache never see
    which path packed a bucket.
    """
    return pack_neighbor_buckets(
        row_idx, col_idx, values, num_rows, num_shards, min_width,
        workspace_elems, features, stable_shapes, options,
    )


def _normal_equations(v, cval, cmask, yty, lam, alpha, implicit, k, matmul_dtype=None):
    """A [C,k,k], b [C,k] of the per-row normal equations given the
    gathered neighbor workspace v [C,D,k] (zeros at masked slots).

    ``matmul_dtype=bfloat16`` runs the Gramian-building einsums with bf16
    operands and f32 accumulation (halved HBM traffic, full-rate MXU);
    the k x k systems and their solves stay f32. Per-row confidence
    weights fold into one operand in f32 BEFORE the cast so the bf16
    rounding applies once per factor entry, not per product term."""
    md = matmul_dtype or jnp.float32
    eye = jnp.eye(k, dtype=jnp.float32)
    pet = dict(preferred_element_type=jnp.float32)
    if implicit:
        conf_m1 = alpha * jnp.abs(cval) * cmask  # c - 1
        vw = (v * conf_m1[..., None]).astype(md)
        a = yty[None] + jnp.einsum("cdk,cdl->ckl", vw, v.astype(md), **pet) + lam * eye[None]
        p = (cval > 0).astype(jnp.float32) * cmask
        bw = ((1.0 + alpha * jnp.abs(cval)) * p).astype(md)
        b = jnp.einsum("cdk,cd->ck", v.astype(md), bw, **pet)
    else:
        n_u = cmask.sum(axis=1)  # ratings per row (ALS-WR lambda scaling)
        vm = v.astype(md)
        a = (
            jnp.einsum("cdk,cdl->ckl", vm, vm, **pet)
            + (lam * jnp.maximum(n_u, 1.0))[:, None, None] * eye[None]
        )
        b = jnp.einsum("cdk,cd->ck", vm, (cval * cmask).astype(md), **pet)
    return a, b


def _sweep_buckets(
    other: jnp.ndarray,  # [M(+1), k] factors of the other side (full copy)
    out_shape: int,  # rows in the output factor matrix (incl. pad slot)
    bucket_args: list[tuple],  # per bucket: (rows, idx, val, deg, chunk)
    lam: float,
    alpha: float,
    implicit: bool,
    matmul_dtype=None,
) -> jnp.ndarray:
    """One half-sweep in replicated-factor mode: solve every bucket and
    scatter results into a fresh [out_shape, k] factor matrix. Rows in no
    bucket (degree 0) stay zero; pad slots (row -1) scatter to the last
    (sacrificial) row, which callers slice off."""
    k = other.shape[1]
    md = matmul_dtype or jnp.float32
    yty = (
        jnp.dot(other.astype(md).T, other.astype(md), preferred_element_type=jnp.float32)
        if implicit
        else None
    )

    def solve_chunk(args):
        cidx, cval, cdeg = args
        cmask = _mask_from_deg(cval.shape, cdeg)
        v = other[cidx] * cmask[..., None]  # [C, D, k]
        a, b = _normal_equations(v, cval, cmask, yty, lam, alpha, implicit, k, md)
        return jnp.linalg.solve(a, b[..., None])[..., 0]

    out = jnp.zeros((out_shape, k), dtype=jnp.float32)
    for rows, idx, val, deg, chunk in bucket_args:
        n, d = idx.shape
        num_chunks = n // chunk
        if num_chunks <= 1:
            solved = solve_chunk((idx, val, deg))
        else:
            solved = jax.lax.map(
                solve_chunk,
                (
                    idx.reshape(num_chunks, chunk, d),
                    val.reshape(num_chunks, chunk, d),
                    deg.reshape(num_chunks, chunk),
                ),
            ).reshape(n, k)
        # pad slots carry row -1 -> scatter to the sacrificial last row
        target = jnp.where(rows < 0, out_shape - 1, rows)
        out = out.at[target].set(solved)
    return out


@dataclass
class ALSModel:
    """Factorization result: row-major float32 factor matrices."""

    x: np.ndarray  # [num_users, k]
    y: np.ndarray  # [num_items, k]


@functools.lru_cache(maxsize=64)
def _compiled_run(
    u_sig: tuple,  # per user-bucket (num_slots, width, chunk)
    i_sig: tuple,  # per item-bucket (num_slots, width, chunk)
    users_pad: int,  # factor rows incl. sacrificial/pow2 pad
    items_pad: int,
    features: int,
    iterations: int,
    implicit: bool,
    matmul_dtype: Optional[str],
    mesh: Optional[Mesh],
):
    """Persistent compiled ALS run, keyed on the static shape signature.

    Everything shape-like is in the cache key; everything value-like
    (bucket contents, init factors, lam, alpha) is a traced argument. A
    warm-started generation whose buckets land on the same pow2 shape
    signature (the common case under ``stable_shapes``) re-enters the
    exact jit wrapper and pays zero tracing and zero XLA compilation —
    previously every ``train_als`` call jitted a fresh closure, so every
    generation recompiled the whole sweep. ``y_init`` is donated: the
    warm-start factors' buffer is reused for the fori_loop carry instead
    of being held live next to it for the whole run.
    """
    md = jnp.bfloat16 if matmul_dtype == "bfloat16" else None
    u_chunks = [c for _, _, c in u_sig]
    i_chunks = [c for _, _, c in i_sig]

    def run(u_arrs, i_arrs, y_init, lam, alpha):
        # chunk sizes are static (from the cache key); arrays + the two
        # hyperparameters are traced, so a lam/alpha sweep is free too
        u_args = [(*a, c) for a, c in zip(u_arrs, u_chunks)]
        i_args = [(*a, c) for a, c in zip(i_arrs, i_chunks)]
        x = jnp.zeros((users_pad, features), dtype=jnp.float32)

        def body(_, carry):
            x_, y_ = carry
            x_ = _sweep_buckets(y_, users_pad, u_args, lam, alpha, implicit, md)
            y_ = _sweep_buckets(x_, items_pad, i_args, lam, alpha, implicit, md)
            return x_, y_

        return jax.lax.fori_loop(0, iterations, body, (x, y_init))

    if mesh is not None:
        repl = NamedSharding(mesh, P())
        return jax.jit(run, out_shardings=(repl, repl), donate_argnums=(2,))
    return jax.jit(run, donate_argnums=(2,))


def compiled_run_cache_info():
    """(hits, misses, ...) of the persistent ALS run cache — exposed for
    the recompile-count regression test and ops introspection."""
    return _compiled_run.cache_info()


def train_als(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    values: np.ndarray,
    num_users: int,
    num_items: int,
    features: int,
    lam: float,
    alpha: float = 1.0,
    implicit: bool = True,
    iterations: int = 10,
    mesh: Optional[Mesh] = None,
    seed: int | None = None,
    workspace_elems: int = 1 << 27,
    shard_factors: bool = False,
    matmul_dtype: str | None = None,
    init_y: np.ndarray | None = None,
    packing: PackingOptions | None = None,
) -> ALSModel:
    """Full ALS training run.

    ``init_y`` [num_items, features] warm-starts the item factors from a
    previous generation (the first half-sweep then solves X against
    near-converged Y instead of noise); rows default to the usual random
    init where the caller has no previous factor (new items). A shape
    mismatch silently falls back to cold init. Replicated-factor path
    only — the sharded path's permuted layout cold-starts.

    COO inputs are int32/float32 numpy arrays. With ``mesh``, neighbor
    buckets are row-sharded over the 'data' axis; factors are replicated
    (default) or, with ``shard_factors=True``, sharded over the mesh so
    factorizations larger than one device's HBM fit the slice (ring-
    exchange half-sweeps; see module docstring). ``workspace_elems``
    bounds the per-chunk gather workspace (elements, not bytes).
    ``matmul_dtype="bfloat16"`` (oryx.batch.compute.matmul-dtype) runs
    the Gramian-building matmuls with bf16 operands and f32 accumulation
    — halved HBM traffic and full-rate MXU on TPU; solves stay f32.
    """
    import time as _time

    from oryx_tpu.common import rng as rng_mod

    t_init = _time.perf_counter()

    if matmul_dtype not in (None, "float32", "bfloat16"):
        # a typo'd dtype silently training full-f32 would corrupt capacity
        # planning; fail at startup like the serving score-dtype check
        raise ValueError(
            f"matmul_dtype must be float32 or bfloat16, got {matmul_dtype!r}"
        )
    md = jnp.bfloat16 if matmul_dtype == "bfloat16" else None
    seed_val = rng_mod.next_seed() if seed is None else seed
    if shard_factors:
        if mesh is None:
            raise ValueError("shard_factors=True requires a mesh")
        return _train_als_sharded(
            user_idx, item_idx, values, num_users, num_items, features,
            lam, alpha, implicit, iterations, mesh, seed_val, workspace_elems,
            md, packing,
        )

    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    t_pack0 = _time.perf_counter()
    u_buckets = build_neighbor_buckets(
        user_idx, item_idx, values, num_users, num_shards,
        workspace_elems=workspace_elems, features=features, options=packing,
    )
    i_buckets = build_neighbor_buckets(
        item_idx, user_idx, values, num_items, num_shards,
        workspace_elems=workspace_elems, features=features, options=packing,
    )
    t_pack = _time.perf_counter() - t_pack0

    # MLlib-style init: small random normal factors (+1 sacrificial pad
    # row, then pow2 row padding so the compiled run's shape signature is
    # stable as the item universe grows; pad rows are zero, enter YtY as
    # zero, and are sliced off on export — numerically free). Host RNG in
    # natural row order so the sharded-factor mode (which permutes the
    # same init) is step-identical with this path.
    users_pad = _pow2_at_least(num_users + 1)
    items_pad = _pow2_at_least(num_items + 1)
    y0 = np.zeros((items_pad, features), np.float32)
    if init_y is not None and np.shape(init_y) == (num_items, features):
        y0[:num_items] = np.asarray(init_y, dtype=np.float32)
    else:
        if init_y is not None:
            # feature count or item universe changed under us: warm-start
            # is an optimization, never a correctness dependency
            log.info(
                "init_y shape %s != (%d, %d); cold-starting",
                np.shape(init_y), num_items, features,
            )
        y0[:num_items] = 0.1 * np.random.default_rng(seed_val).standard_normal(
            (num_items, features)
        ).astype(np.float32)

    u_sig = tuple((b.num_slots, b.width, b.chunk) for b in u_buckets)
    i_sig = tuple((b.num_slots, b.width, b.chunk) for b in i_buckets)
    run_c = _compiled_run(
        u_sig, i_sig, users_pad, items_pad, features, iterations, implicit,
        matmul_dtype, mesh,
    )

    def to_arrs(buckets, row_sh=None, row_sh2=None):
        out = []
        for b in buckets:
            if row_sh is None:
                out.append((jnp.asarray(b.rows), jnp.asarray(b.idx), jnp.asarray(b.val), jnp.asarray(b.deg)))
            else:
                out.append(
                    (
                        jax.device_put(b.rows, row_sh),
                        jax.device_put(b.idx, row_sh2),
                        jax.device_put(b.val, row_sh2),
                        jax.device_put(b.deg, row_sh),
                    )
                )
        return out

    lam_t = jnp.float32(lam)
    alpha_t = jnp.float32(alpha)
    t_iter = _time.perf_counter()
    if mesh is not None:
        row_sharded = NamedSharding(mesh, P(DATA_AXIS))
        row_sharded2 = NamedSharding(mesh, P(DATA_AXIS, None))
        repl = NamedSharding(mesh, P())
        u_arrs = to_arrs(u_buckets, row_sharded, row_sharded2)
        i_arrs = to_arrs(i_buckets, row_sharded, row_sharded2)
        y0 = jax.device_put(np.asarray(y0), repl)
        log.info(
            "ALS over %d devices, factors replicated, widest user bucket shards: %s",
            num_shards, shard_layout(u_arrs[-1][1]),
        )
        x, y = run_c(u_arrs, i_arrs, y0, lam_t, alpha_t)
    else:
        x, y = run_c(
            to_arrs(u_buckets), to_arrs(i_buckets), jnp.asarray(y0), lam_t, alpha_t
        )

    x = np.asarray(x)[:num_users]
    y = np.asarray(y)[:num_items]
    last_phase_seconds.clear()
    last_phase_seconds.update(
        pack=t_pack,
        init=t_iter - t_init - t_pack,
        iterate=_time.perf_counter() - t_iter,
    )
    return ALSModel(x=x, y=y)


# ---------------------------------------------------------------------------
# Sharded-factor training: ring-exchange half-sweeps under shard_map
# ---------------------------------------------------------------------------


def _sharded_layout(buckets: list[NeighborBucket], num_rows: int, s: int):
    """Device-major slot layout for sharded factors.

    Global slot order is device-major, bucket-minor: device d's block is
    the concatenation of every bucket's d-th shard slice. Returns
    (perm_rows [T] global row id per slot (-1 pad), pos [num_rows] slot
    position per row (-1 if degree 0), loc = slots per device)."""
    loc = sum(b.num_slots // s for b in buckets)
    total = loc * s
    perm_rows = np.full(total, -1, dtype=np.int64)
    pos = np.full(num_rows, -1, dtype=np.int64)
    offset = 0
    for b in buckets:
        n_b = b.num_slots
        n_loc = n_b // s
        i = np.arange(n_b)
        d, j = i // n_loc, i % n_loc
        gp = d * loc + offset + j
        perm_rows[gp] = b.rows
        valid = b.rows >= 0
        pos[b.rows[valid]] = gp[valid]
        offset += n_loc
    return perm_rows, pos, loc


def _translate_to_shards(idx: np.ndarray, pos_other: np.ndarray, other_loc: int):
    """Map col ids to (owner shard, local row) in the other side's layout.

    Entries whose col has no slot (only possible for mask-0 padding, idx
    0) get shard -1 — matched by no ring step, contributing zero."""
    p = pos_other[idx]
    ish = np.where(p < 0, -1, p // other_loc).astype(np.int32)
    ilo = np.where(p < 0, 0, p % other_loc).astype(np.int32)
    return ish, ilo


def _train_als_sharded(
    user_idx, item_idx, values, num_users, num_items, features,
    lam, alpha, implicit, iterations, mesh, seed_val, workspace_elems,
    matmul_dtype=None, packing=None,
) -> ALSModel:
    """shard_map ALS with factors sharded over the mesh (see module doc)."""
    from jax import shard_map

    s = int(np.prod(mesh.devices.shape))
    u_buckets = build_neighbor_buckets(
        user_idx, item_idx, values, num_users, s,
        workspace_elems=workspace_elems, features=features, options=packing,
    )
    i_buckets = build_neighbor_buckets(
        item_idx, user_idx, values, num_items, s,
        workspace_elems=workspace_elems, features=features, options=packing,
    )
    if not u_buckets or not i_buckets:
        return ALSModel(
            x=np.zeros((num_users, features), np.float32),
            y=np.zeros((num_items, features), np.float32),
        )

    perm_x, pos_x, u_loc = _sharded_layout(u_buckets, num_users, s)
    perm_y, pos_y, i_loc = _sharded_layout(i_buckets, num_items, s)

    u_arrs = []
    for b in u_buckets:
        ish, ilo = _translate_to_shards(b.idx, pos_y, i_loc)
        u_arrs.append((ish, ilo, b.val, b.deg))
    i_arrs = []
    for b in i_buckets:
        ish, ilo = _translate_to_shards(b.idx, pos_x, u_loc)
        i_arrs.append((ish, ilo, b.val, b.deg))
    u_chunks = [b.chunk for b in u_buckets]
    i_chunks = [b.chunk for b in i_buckets]

    # same natural-order init as the replicated path, permuted into the
    # sharded layout (pad slots zero — they enter the psum'd YtY)
    y_nat = 0.1 * np.random.default_rng(seed_val).standard_normal(
        (num_items, features)
    ).astype(np.float32)
    y0 = np.zeros((i_loc * s, features), np.float32)
    yv0 = perm_y >= 0
    y0[yv0] = y_nat[perm_y[yv0]]

    ring = [(i, (i - 1) % s) for i in range(s)]
    k = features

    def ring_fill(other_loc, ish_c, ilo_c):
        """[C, D, k] workspace: at ring step t this device holds the other
        side's shard (my+t) mod S and fills the slots that shard owns."""
        my = jax.lax.axis_index(DATA_AXIS)
        v0 = jnp.zeros(ish_c.shape + (other_loc.shape[1],), jnp.float32)
        # the accumulator varies per device (ppermute output feeds it):
        # mark it device-varying so the scan carry types line up
        v0 = _pcast_varying(v0)

        def step(carry, t):
            cur, v = carry
            shard_id = jax.lax.rem(my + t, s)
            g = cur[ilo_c]
            v = v + jnp.where((ish_c == shard_id)[..., None], g, 0.0)
            cur = jax.lax.ppermute(cur, DATA_AXIS, ring)
            return (cur, v), None

        (_, v), _ = jax.lax.scan(step, (other_loc, v0), jnp.arange(s, dtype=jnp.int32))
        return v

    def half_sweep(other_loc, arrs, chunks):
        md = matmul_dtype or jnp.float32
        yty = (
            jax.lax.psum(
                jnp.dot(
                    other_loc.astype(md).T,
                    other_loc.astype(md),
                    preferred_element_type=jnp.float32,
                ),
                DATA_AXIS,
            )
            if implicit
            else None
        )
        outs = []
        for (ish, ilo, val, deg), chunk in zip(arrs, chunks):
            n_loc, d = ish.shape

            def solve_chunk(args):
                ish_c, ilo_c, cval, cdeg = args
                cmask = _mask_from_deg(cval.shape, cdeg)
                v = ring_fill(other_loc, ish_c, ilo_c) * cmask[..., None]
                a, b = _normal_equations(v, cval, cmask, yty, lam, alpha, implicit, k, md)
                return jnp.linalg.solve(a, b[..., None])[..., 0]

            nch = n_loc // chunk
            if nch <= 1:
                solved = solve_chunk((ish, ilo, val, deg))
            else:
                solved = jax.lax.map(
                    solve_chunk,
                    (
                        ish.reshape(nch, chunk, d),
                        ilo.reshape(nch, chunk, d),
                        val.reshape(nch, chunk, d),
                        deg.reshape(nch, chunk),
                    ),
                ).reshape(n_loc, k)
            outs.append(solved)
        return jnp.concatenate(outs, axis=0)

    def run(u_in, i_in, y_loc0):
        def body(_, carry):
            x_loc, y_loc = carry
            x_loc = half_sweep(y_loc, u_in, u_chunks)
            y_loc = half_sweep(x_loc, i_in, i_chunks)
            return x_loc, y_loc

        x_loc = _pcast_varying(jnp.zeros((u_loc, features), jnp.float32))
        return jax.lax.fori_loop(0, iterations, body, (x_loc, y_loc0))

    spec2 = P(DATA_AXIS, None)
    spec1 = P(DATA_AXIS)  # the rank-1 per-slot degree column
    arr_specs_u = [(spec2, spec2, spec2, spec1) for _ in u_arrs]
    arr_specs_i = [(spec2, spec2, spec2, spec1) for _ in i_arrs]
    run_c = jax.jit(
        shard_map(
            run,
            mesh=mesh,
            in_specs=(arr_specs_u, arr_specs_i, spec2),
            out_specs=(spec2, spec2),
        )
    )

    sh2 = NamedSharding(mesh, spec2)
    sh1 = NamedSharding(mesh, spec1)
    u_dev = [
        tuple(jax.device_put(a, sh1 if a.ndim == 1 else sh2) for a in t)
        for t in u_arrs
    ]
    i_dev = [
        tuple(jax.device_put(a, sh1 if a.ndim == 1 else sh2) for a in t)
        for t in i_arrs
    ]
    x_p, y_p = run_c(u_dev, i_dev, jax.device_put(y0, sh2))
    log.info("ALS over %d devices, item factor shards: %s", s, shard_layout(y_p))

    x = np.zeros((num_users, features), np.float32)
    y = np.zeros((num_items, features), np.float32)
    xv = perm_x >= 0
    yv = perm_y >= 0
    x[perm_x[xv]] = np.asarray(x_p)[xv]
    y[perm_y[yv]] = np.asarray(y_p)[yv]
    return ALSModel(x=x, y=y)


# -- evaluation --------------------------------------------------------------


def predict_pairs(x: np.ndarray, y: np.ndarray, user_idx: np.ndarray, item_idx: np.ndarray) -> np.ndarray:
    """Predicted strengths for (user, item) pairs (on device, batched)."""

    @jax.jit
    def _pred(xa, ya, ui, ii):
        return jnp.sum(xa[ui] * ya[ii], axis=-1)

    return np.asarray(_pred(x, y, user_idx, item_idx))


def rmse(x: np.ndarray, y: np.ndarray, user_idx, item_idx, values) -> float:
    """Root mean squared error over test pairs (Evaluation.rmse analogue,
    app/oryx-app-mllib/.../als/Evaluation.java:49-63)."""
    if len(values) == 0:
        return float("nan")
    pred = predict_pairs(x, y, user_idx, item_idx)
    return float(np.sqrt(np.mean((pred - values) ** 2)))


@functools.partial(jax.jit, static_argnames=("chunk",))
def _auc_bucket_jit(x, y, uids, pos, posm, neg, negm, chunk):
    """Per-user AUC for one degree bucket: [N, P] padded positive and
    sampled-negative item ids + masks. Scores on the MXU, pairwise
    comparison [C, P, P] chunked to bound memory."""

    def per_chunk(args):
        cu, cp, cpm, cn, cnm = args
        xu = x[cu]  # [C, k]
        sp = jnp.einsum("cpk,ck->cp", y[cp], xu)
        sn = jnp.einsum("cnk,ck->cn", y[cn], xu)
        gt = (
            (sp[:, :, None] > sn[:, None, :])
            & cpm[:, :, None]
            & cnm[:, None, :]
        ).sum(axis=(1, 2))
        pairs = cpm.sum(axis=1) * cnm.sum(axis=1)
        return gt / jnp.maximum(pairs, 1), pairs > 0

    n = uids.shape[0]
    if n <= chunk:
        return per_chunk((uids, pos, posm, neg, negm))
    nch = n // chunk
    a, v = jax.lax.map(
        per_chunk,
        (
            uids.reshape(nch, chunk),
            pos.reshape(nch, chunk, -1),
            posm.reshape(nch, chunk, -1),
            neg.reshape(nch, chunk, -1),
            negm.reshape(nch, chunk, -1),
        ),
    )
    return a.reshape(n), v.reshape(n)


def mean_auc(
    x: np.ndarray,
    y: np.ndarray,
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    rng: np.random.Generator,
) -> float:
    """Mean per-user AUC with about as many sampled negatives as positives
    per user (Evaluation.areaUnderCurve, Evaluation.java:70-136).

    Fully vectorized (VERDICT r1 #8): users are grouped into power-of-two
    positive-count buckets; negative sampling (4x candidates, positives
    rejected) happens with one sort + searchsorted pass per bucket on
    host, and the score/pairwise-comparison work runs on device with
    chunked [C, P, P] comparisons — no Python per-user loop."""
    if len(user_idx) == 0:
        return float("nan")
    all_items = np.unique(item_idx)
    order = np.argsort(user_idx, kind="stable")
    uu, ii = user_idx[order], item_idx[order]
    uniq_users, starts = np.unique(uu, return_index=True)
    ends = np.concatenate([starts[1:], [len(uu)]])
    counts = ends - starts

    xd = jnp.asarray(x, dtype=jnp.float32)
    yd = jnp.asarray(y, dtype=jnp.float32)

    # per-entry user ordinal and position within the user's run
    entry_user = np.repeat(np.arange(len(uniq_users)), counts)
    entry_pos = np.arange(len(ii)) - np.repeat(starts, counts)

    aucs: list[np.ndarray] = []
    valids: list[np.ndarray] = []
    widths = np.maximum(1, 2 ** np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64))
    for w in sorted(set(widths.tolist())):
        sel = np.flatnonzero(widths == w)
        nu = len(sel)
        p = int(w)
        pos = np.zeros((nu, p), dtype=np.int64)
        posm = np.zeros((nu, p), dtype=bool)
        slot_of = np.full(len(uniq_users), -1, dtype=np.int64)
        slot_of[sel] = np.arange(nu)
        esel = slot_of[entry_user] >= 0
        pos[slot_of[entry_user[esel]], entry_pos[esel]] = ii[esel]
        posm[slot_of[entry_user[esel]], entry_pos[esel]] = True
        # sample 4x candidates, reject positives via disjoint-range keys:
        # row r's sorted positives become keys in [r*M, (r+1)*M) so one
        # global searchsorted answers rowwise membership
        m = int(all_items.max()) + 2
        cand = rng.choice(all_items, size=(nu, 4 * p))
        keys = np.sort(np.where(posm, pos, m - 1) + np.arange(nu)[:, None] * m, axis=1)
        ckeys = cand + np.arange(nu)[:, None] * m
        loc = np.searchsorted(keys.ravel(), ckeys.ravel())
        hit = np.zeros(loc.shape, dtype=bool)
        in_range = loc < keys.size
        hit[in_range] = keys.ravel()[loc[in_range]] == ckeys.ravel()[in_range]
        ok = ~hit.reshape(nu, 4 * p)
        rank = np.cumsum(ok, axis=1) - 1
        want = counts[sel][:, None]  # as many negatives as positives
        take = ok & (rank < want) & (rank < p)
        neg = np.zeros((nu, p), dtype=np.int64)
        negm = np.zeros((nu, p), dtype=bool)
        rows, cols = np.nonzero(take)
        neg[rows, rank[rows, cols]] = cand[rows, cols]
        negm[rows, rank[rows, cols]] = True

        chunk = max(1, min(nu, (1 << 24) // max(p * p, 1)))
        pad = -nu % chunk
        if pad:
            z2 = np.zeros((pad, p), dtype=np.int64)
            zb = np.zeros((pad, p), dtype=bool)
            pos, posm = np.concatenate([pos, z2]), np.concatenate([posm, zb])
            neg, negm = np.concatenate([neg, z2]), np.concatenate([negm, zb])
        uids = np.concatenate([uniq_users[sel], np.zeros(pad, uniq_users.dtype)])
        a, v = _auc_bucket_jit(
            xd, yd, jnp.asarray(uids), jnp.asarray(pos), jnp.asarray(posm),
            jnp.asarray(neg), jnp.asarray(negm), chunk,
        )
        aucs.append(np.asarray(a)[:nu])
        valids.append(np.asarray(v)[:nu])
    auc = np.concatenate(aucs)
    valid = np.concatenate(valids)
    return float(auc[valid].mean()) if valid.any() else float("nan")


# ---------------------------------------------------------------------------
# Speed-layer fold-in, batched on device
# ---------------------------------------------------------------------------
#
# The reference folds in one event at a time (ALSUtils.computeUpdatedXu:
# 74-106 inside ALSSpeedModelManager.buildUpdates' parallelStream). All
# events in a micro-batch read the PRE-batch model state (updates travel
# via the update topic, not in-place), so the whole batch is one
# data-parallel computation: a single [k,k] Cholesky factorization per
# side reused against an [n,k] right-hand-side block on the MXU.


def _batch_target_qui(implicit: bool, values, current):
    """Vectorized ALSUtils.computeTargetQui:37-59; NaN = no update."""
    if not implicit:
        return values
    pos = (values > 0.0) & (current < 1.0)
    t_pos = current + (values / (1.0 + values)) * (1.0 - jnp.maximum(0.0, current))
    neg = (values < 0.0) & (current > 0.0)
    t_neg = current + (values / (values - 1.0)) * (0.0 - jnp.minimum(1.0, current))
    return jnp.where(pos, t_pos, jnp.where(neg, t_neg, jnp.nan))


def _fold_half(ata, vecs_own, own_valid, vecs_other, other_valid, values, implicit):
    """New own-side vectors after events against the other side's vectors.

    vecs_own[n,k] current vectors (zeros where own_valid is False — a
    brand-new row starts from a "don't know" prior of 0.5), vecs_other
    the interacting vectors. Returns (new_vecs[n,k], updated[n])."""
    qui = jnp.where(own_valid, jnp.sum(vecs_own * vecs_other, axis=1), 0.0)
    current = jnp.where(own_valid, qui, 0.5)
    target = _batch_target_qui(implicit, values, current)
    d_qui = target - qui
    rhs = d_qui[:, None] * vecs_other  # [n, k]
    chol = jax.scipy.linalg.cho_factor(ata)
    d_vec = jax.scipy.linalg.cho_solve(chol, rhs.T).T
    # Cholesky of a near-singular AtA yields NaNs in float32 (the host
    # Solver's QR threshold/lstsq fallback has no device analogue), so
    # whole rows that came out non-finite are re-solved via pseudo-inverse
    # rather than published corrupted. lax.cond keeps the SVD off the hot
    # path when the factorization was healthy (the common case).
    row_ok = jnp.all(jnp.isfinite(d_vec), axis=1, keepdims=True)
    d_vec = jax.lax.cond(
        jnp.all(row_ok),
        lambda d, _a, _r: d,
        lambda d, a, r: jnp.where(row_ok, d, (jnp.linalg.pinv(a, rcond=1e-5) @ r.T).T),
        d_vec,
        ata,
        rhs,
    )
    new_vecs = jnp.where(own_valid[:, None], vecs_own, 0.0) + d_vec
    updated = other_valid & ~jnp.isnan(target) & jnp.all(jnp.isfinite(d_vec), axis=1)
    return jnp.where(updated[:, None], new_vecs, 0.0), updated


@functools.partial(jax.jit, static_argnames=("implicit",))
def _fold_in_batch_jit(yty, xtx, xu, xu_valid, yi, yi_valid, values, implicit):
    new_xu, x_upd = _fold_half(yty, xu, xu_valid, yi, yi_valid, values, implicit)
    new_yi, y_upd = _fold_half(xtx, yi, yi_valid, xu, xu_valid, values, implicit)
    return new_xu, x_upd, new_yi, y_upd


def _fold_half_host(ata, vecs_own, own_valid, vecs_other, other_valid, values, implicit):
    """Host (BLAS) twin of _fold_half: float32 vectors/solves (same
    precision as the device path), float64 target math (scalar parity)."""
    vo = np.asarray(vecs_own, dtype=np.float32)
    vt = np.asarray(vecs_other, dtype=np.float32)
    values = values.astype(np.float64)
    qui = np.where(own_valid, np.einsum("nk,nk->n", vo, vt, dtype=np.float64), 0.0)
    current = np.where(own_valid, qui, 0.5)
    if implicit:
        with np.errstate(divide="ignore", invalid="ignore"):
            t_pos = current + (values / (1.0 + values)) * (1.0 - np.maximum(0.0, current))
            t_neg = current + (values / (values - 1.0)) * (0.0 - np.minimum(1.0, current))
        target = np.where(
            (values > 0.0) & (current < 1.0),
            t_pos,
            np.where((values < 0.0) & (current > 0.0), t_neg, np.nan),
        )
    else:
        target = values
    d_qui = np.nan_to_num(target - qui).astype(np.float32)
    rhs = d_qui[:, None] * vt
    ata32 = np.asarray(ata, dtype=np.float32)
    try:
        # AtA is SPD and k x k (tiny): invert it ONCE via Cholesky (in
        # float64 for the inversion's sake), then apply to all n right-hand
        # sides as a single GEMM. One n*k^2 GEMM is ~2x the two BLAS
        # triangular solves cho_solve costs over the same n — this is the
        # speed layer's per-event floor at 100K events/s. The pinv
        # fallback below still catches ill-conditioned Gramians.
        import scipy.linalg as sla

        chol = sla.cho_factor(ata32.astype(np.float64), lower=True, check_finite=False)
        ainv = sla.cho_solve(
            chol, np.eye(ata32.shape[0], dtype=np.float64), check_finite=False
        ).astype(np.float32)
        d_vec = rhs @ ainv  # ainv symmetric: no transpose needed
    except Exception:
        d_vec = np.full_like(rhs, np.nan)
    # same safety net as the device path: singular/ill-conditioned AtA
    # falls back to a pseudo-inverse solve, and rows that still come out
    # non-finite are dropped instead of published
    finite = np.isfinite(d_vec).all(axis=1)
    if not finite.all():
        d_lstsq = (np.linalg.pinv(ata32, rcond=1e-5) @ rhs.T).T
        d_vec = np.where(~finite[:, None], d_lstsq, d_vec)
        finite = np.isfinite(d_vec).all(axis=1)
    new = np.where(own_valid[:, None], vo, 0.0)
    new += d_vec  # in-place: [n,k] temp saved, bits unchanged
    updated = other_valid & ~np.isnan(target) & finite
    if not updated.all():  # zero dropped rows in place of a full where-copy
        new[~updated] = 0.0
    return new.astype(np.float32, copy=False), updated


def _bucket(n: int) -> int:
    """Pad batch sizes to power-of-two buckets so the jitted fold-in
    compiles once per bucket, not once per micro-batch size."""
    return max(256, 1 << (n - 1).bit_length())


_auto_fold_choice: str | None = None


def _calibrate_fold_backend(yty, xtx, xu, xu_valid, yi, yi_valid, values, implicit):
    """Time host vs device on this real batch, lock in the winner, return
    the host result (already computed — no work wasted). The device is
    timed on a second call so compile time doesn't poison the measurement."""
    global _auto_fold_choice
    import time as _time

    t0 = _time.perf_counter()
    host_result = fold_in_batch(
        yty, xtx, xu, xu_valid, yi, yi_valid, values, implicit, backend="host"
    )
    t_host = _time.perf_counter() - t0
    # a device error here propagates: electing the host on a failed
    # device would hide that the process is not running where it says
    fold_in_batch(  # compile + first dispatch, untimed
        yty, xtx, xu, xu_valid, yi, yi_valid, values, implicit, backend="device"
    )
    t0 = _time.perf_counter()
    fold_in_batch(
        yty, xtx, xu, xu_valid, yi, yi_valid, values, implicit, backend="device"
    )
    t_device = _time.perf_counter() - t0
    _auto_fold_choice = "device" if t_device < t_host else "host"
    log.info(
        "fold-in auto backend: host %.3fs vs device %.3fs at n=%d -> %s",
        t_host, t_device, len(values), _auto_fold_choice,
    )
    return host_result


def fold_in_batch(
    yty: np.ndarray,
    xtx: np.ndarray,
    xu: np.ndarray,
    xu_valid: np.ndarray,
    yi: np.ndarray,
    yi_valid: np.ndarray,
    values: np.ndarray,
    implicit: bool,
    backend: str = "auto",
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Fold a micro-batch of n (user, item, value) events into both factor
    sides at once. xu/yi are the events' current vectors ([n,k], zero rows
    where the id is new, flagged by the valid masks). Returns
    (new_xu[n,k], x_updated[n], new_yi[n,k], y_updated[n]) — rows where
    the updated flag is False carry no update (reference: None returns of
    ALSUtils.computeUpdatedXu).

    backend: 'device' (jit, batch padded to power-of-two buckets),
    'host' (float64 BLAS), or 'auto' — measured, not guessed: the first
    large enough batch runs both backends once, times them, and locks in
    the winner for the process. A size heuristic cannot know the
    deployment's dispatch latency, and guessing wrong costs 2-3x
    sustained speed-layer throughput."""
    n, k = xu.shape
    if backend == "auto":
        if _auto_fold_choice is not None:
            backend = _auto_fold_choice
        elif n * max(k, 1) < 500_000:
            backend = "host"  # too small to learn from; host wins when tiny
        else:
            return _calibrate_fold_backend(
                yty, xtx, xu, xu_valid, yi, yi_valid, values, implicit
            )
    if backend == "host":
        new_xu, x_upd = _fold_half_host(yty, xu, xu_valid, yi, yi_valid, values, implicit)
        new_yi, y_upd = _fold_half_host(xtx, yi, yi_valid, xu, xu_valid, values, implicit)
        return new_xu, x_upd, new_yi, y_upd
    m = _bucket(n)
    if m != n:
        pad = m - n
        xu = np.concatenate([xu, np.zeros((pad, k), xu.dtype)])
        yi = np.concatenate([yi, np.zeros((pad, k), yi.dtype)])
        xu_valid = np.concatenate([xu_valid, np.zeros(pad, bool)])
        yi_valid = np.concatenate([yi_valid, np.zeros(pad, bool)])
        values = np.concatenate([values, np.zeros(pad, values.dtype)])
    out = _fold_in_batch_jit(
        jnp.asarray(yty, dtype=jnp.float32),
        jnp.asarray(xtx, dtype=jnp.float32),
        jnp.asarray(xu, dtype=jnp.float32),
        jnp.asarray(xu_valid),
        jnp.asarray(yi, dtype=jnp.float32),
        jnp.asarray(yi_valid),
        jnp.asarray(values, dtype=jnp.float32),
        implicit,
    )
    new_xu, x_upd, new_yi, y_upd = (np.asarray(o)[:n] for o in out)
    return new_xu, x_upd, new_yi, y_upd


def device_gramian(mat: np.ndarray):
    """Upload a [k,k] Gramian once as a float32 device array. Callers
    cache the result on the owning Solver instance: the solver cache is
    invalidated exactly when the Gramian changes (vector writes, model
    rotation), so a fresh Solver — not every micro-batch — is the only
    event that pays the host->device round-trip again."""
    return jnp.asarray(np.asarray(mat), dtype=jnp.float32)


class FoldInSession:
    """Accumulate fold-in delta blocks and solve them as one micro-batch.

    The pipelined speed layer parses the input stream into several event
    blocks per micro-batch (one per transport frame). Folding each block
    separately would pay a Cholesky + dispatch per block; a session
    accumulates the gathered vector blocks as they arrive — eagerly
    placed on device when the fold backend is the device, so the
    host->device copies overlap the parse stage — and issues ONE solve
    over the concatenation per micro-batch.

    ``yty``/``xtx`` may be numpy arrays or device arrays from
    :func:`device_gramian`; device-resident Gramians flow into the jitted
    solve with no per-batch transfer. Results are computed by the exact
    same code as :func:`fold_in_batch` (the host path literally calls
    it), so a session is bit-identical to the unbatched fold at f32.
    """

    def __init__(self, yty, xtx, implicit: bool, backend: str = "auto") -> None:
        self.yty = yty
        self.xtx = xtx
        self.implicit = implicit
        self.backend = backend
        # which side computed the last solve()'s result: "device" or "host"
        # (set by the branch that ran, not read from the switch)
        self.ran: str | None = None
        self._blocks: list[tuple] = []
        self._pending = 0
        from oryx_tpu.common import ledger

        # released by reference drop (the device Gramians/blocks live as
        # long as the session) — no probe, live while strongly referenced
        ledger.register("session", self)

    def _resolved_backend(self, n: int, k: int) -> str:
        if self.backend != "auto":
            return self.backend
        if _auto_fold_choice is not None:
            return _auto_fold_choice
        return "host" if n * max(k, 1) < 500_000 else "auto"

    def resolved_backend(self, n: int, k: int) -> str:
        """The backend this session would pick for an [n,k] micro-batch.
        Callers use it to decide whether device-resident Gramians are
        worth handing in: the host path wants the float64 originals (its
        Cholesky runs in f64), the device path casts to f32 regardless."""
        return self._resolved_backend(n, k)

    def add_block(self, xu, xu_valid, yi, yi_valid, values) -> None:
        n, k = xu.shape
        if self._resolved_backend(max(self._pending + n, n), k) == "device":
            block = (
                jnp.asarray(xu, dtype=jnp.float32),
                jnp.asarray(xu_valid),
                jnp.asarray(yi, dtype=jnp.float32),
                jnp.asarray(yi_valid),
                jnp.asarray(values, dtype=jnp.float32),
            )
        else:
            block = (xu, xu_valid, yi, yi_valid, values)
        self._blocks.append(block)
        self._pending += n

    @property
    def pending(self) -> int:
        return self._pending

    def solve(self):
        """One fold over everything accumulated; clears the session.
        Returns (new_xu, x_updated, new_yi, y_updated) like fold_in_batch,
        or None when nothing is pending."""
        if not self._blocks:
            return None
        blocks, self._blocks = self._blocks, []
        n, self._pending = self._pending, 0
        k = blocks[0][0].shape[1]
        backend = self._resolved_backend(n, k)
        if backend == "device" and all(
            isinstance(b[0], jnp.ndarray) for b in blocks
        ):
            # all-device micro-batch: concatenate + pad on device and call
            # the jitted kernel with the resident Gramians directly — the
            # only host traffic is the [n,k] results coming back
            self.ran = "device"
            xu, xu_valid, yi, yi_valid, values = (
                b[0] if len(blocks) == 1 else jnp.concatenate([blk[i] for blk in blocks])
                for i, b in enumerate(zip(*blocks))
            )
            m = _bucket(n)
            if m != n:
                pad = m - n
                xu = jnp.concatenate([xu, jnp.zeros((pad, k), xu.dtype)])
                yi = jnp.concatenate([yi, jnp.zeros((pad, k), yi.dtype)])
                xu_valid = jnp.concatenate([xu_valid, jnp.zeros(pad, bool)])
                yi_valid = jnp.concatenate([yi_valid, jnp.zeros(pad, bool)])
                values = jnp.concatenate([values, jnp.zeros(pad, values.dtype)])
            out = _fold_in_batch_jit(
                jnp.asarray(self.yty, dtype=jnp.float32),
                jnp.asarray(self.xtx, dtype=jnp.float32),
                xu, xu_valid, yi, yi_valid, values, self.implicit,
            )
            new_xu, x_upd, new_yi, y_upd = (np.asarray(o)[:n] for o in out)
            return new_xu, x_upd, new_yi, y_upd
        cat = [
            b[0] if len(blocks) == 1 else np.concatenate([np.asarray(blk[i]) for blk in blocks])
            for i, b in enumerate(zip(*blocks))
        ]
        # "auto" still calibrating returns the host's result
        self.ran = "device" if backend == "device" else "host"
        return fold_in_batch(
            np.asarray(self.yty),
            np.asarray(self.xtx),
            *cat,
            self.implicit,
            backend=backend,
        )


class PartitionedFoldInSession:
    """Sharded fold-in: K disjoint accumulator slices over ONE shared
    Gramian pair.

    The sharded speed pipeline runs K independent parse->fold->publish
    chains; each chain folds only its own partitions' events. A naive
    per-shard :class:`FoldInSession` would re-upload the Gramians per
    shard per micro-batch; here every slice shares the same ``yty``/
    ``xtx`` references (device-resident via :func:`device_gramian` when
    the backend resolves there — uploaded ONCE for all K shards), and
    each shard's blocks accumulate in its own slice so concurrent
    ``add_block``/``solve_shard`` calls never touch shared state.

    Bit-identity: the fold math is row-wise independent — each event row
    gets its own einsum/target/GEMM against the same fixed Gramians (see
    ``_fold_half_host`` / ``_fold_half``) — so folding a shard's slice
    alone, or merging all slices into one solve (:meth:`solve`, shard
    order), produces EXACTLY the f32 bits a single session fed the same
    events would. Tests assert both forms against ``FoldInSession``.
    """

    def __init__(self, yty, xtx, implicit: bool, shards: int, backend: str = "auto") -> None:
        if shards < 1:
            raise ValueError("shards must be >= 1")
        self.implicit = implicit
        self.backend = backend
        self._slices = [
            FoldInSession(yty, xtx, implicit, backend) for _ in range(shards)
        ]

    @property
    def shards(self) -> int:
        return len(self._slices)

    @property
    def pending(self) -> int:
        return sum(s.pending for s in self._slices)

    def set_gramians(self, yty, xtx) -> None:
        """Swap in (typically device-resident) Gramians for every slice —
        one upload serves all K shards for the life of the Solver pair."""
        for s in self._slices:
            s.yty = yty
            s.xtx = xtx

    def session(self, shard: int) -> FoldInSession:
        """Shard ``shard``'s private slice. Distinct shards may use their
        slices concurrently; one shard's slice is single-threaded."""
        return self._slices[shard % len(self._slices)]

    def resolved_backend(self, n: int, k: int) -> str:
        return self._slices[0].resolved_backend(n, k)

    def add_block(self, shard: int, xu, xu_valid, yi, yi_valid, values) -> None:
        self.session(shard).add_block(xu, xu_valid, yi, yi_valid, values)

    def solve_shard(self, shard: int):
        """Fold shard ``shard``'s accumulated slice alone (its micro-batch
        boundary); other shards' slices are untouched."""
        return self.session(shard).solve()

    def solve(self):
        """The merge step: reconcile ALL slices in shard order into one
        solve — the cheap cross-shard synchronization point (list moves
        only; the concatenation happens inside the single solve)."""
        merged = self._slices[0]
        for s in self._slices[1:]:
            merged._blocks.extend(s._blocks)
            merged._pending += s._pending
            s._blocks = []
            s._pending = 0
        return merged.solve()
