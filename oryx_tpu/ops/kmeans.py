"""K-means on TPU: Lloyd iterations and internal evaluation metrics.

The TPU-native replacement for Spark MLlib's KMeans.train used by the
reference's KMeansUpdate (app/oryx-app-mllib/.../kmeans/KMeansUpdate.java:
116-117): one Lloyd iteration is a distance matmul ([n,d] @ [d,k] on the
MXU), an argmin, and segment-sum reductions — points row-sharded over the
mesh's 'data' axis, centers replicated, XLA reducing partial sums across
shards. Initialization: "random" or "k-means||" (Bahmani et al.;
MLlib's default init, oversample then weighted k-means++ on candidates).

Also the four internal clustering quality metrics the reference computes
as Spark map-reduces (SumSquaredError/DaviesBouldinIndex/DunnIndex/
SilhouetteCoefficient.java, SURVEY.md §2.8), vectorized.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oryx_tpu.parallel.mesh import DATA_AXIS, pad_to_multiple


# one-hot [n, k] element cap for the matmul centroid update: beyond it
# (huge n*k) the memory-lean scatter update takes over
_ONEHOT_ELEM_BUDGET = 1 << 27

# wall seconds of the most recent train_kmeans call, split by phase
# ({"init": s, "iterate": s}); read by tools/train_benchmark.py.
# Overwritten per call, never merged.
last_phase_seconds: dict[str, float] = {}


def _assign(points_, centers_, mask_):
    # HIGHEST: the TPU default would compute distances in bf16 passes,
    # flipping borderline argmin assignments vs the Pallas sweep (which
    # accumulates in f32) and drifting the centers apart
    d2 = (
        jnp.sum(points_ * points_, axis=1, keepdims=True)
        - 2.0
        * jnp.dot(
            points_,
            centers_.T,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        + jnp.sum(centers_ * centers_, axis=1)[None, :]
    )
    a = jnp.argmin(d2, axis=1)
    mind2 = jnp.min(d2, axis=1)
    return a, jnp.where(mask_, mind2, 0.0)


def _centroid_sums(points, a, w, k):
    """Per-cluster (sums [k, d], counts [k]) of `w`-weighted points. The
    one-hot matmul form keeps the reduction on the MXU (and is several
    times faster than an XLA:CPU scatter); the segment-sum form is the
    fallback when the [n, k] one-hot would be too large."""
    if points.shape[0] * k <= _ONEHOT_ELEM_BUDGET:
        oh = jax.nn.one_hot(a, k, dtype=points.dtype) * w[:, None]
        sums = jnp.dot(oh.T, points, preferred_element_type=jnp.float32)
        counts = jnp.sum(oh, axis=0)
    else:
        sums = jax.ops.segment_sum(points * w[:, None], a, num_segments=k)
        counts = jax.ops.segment_sum(w, a, num_segments=k)
    return sums, counts


@functools.partial(jax.jit, static_argnums=3)
def _lloyd_run(points, centers0, mask, iterations):
    """points [n, d], centers0 [k, d], mask [n] bool (False = padding row)."""

    def body(_, centers_):
        a, _d = _assign(points, centers_, mask)
        k = centers_.shape[0]
        w = mask.astype(points.dtype)
        sums, counts = _centroid_sums(points, a, w, k)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers_
        )
        return new_centers

    centers = jax.lax.fori_loop(0, iterations, body, centers0)
    a, d2 = _assign(points, centers, mask)
    w = mask.astype(points.dtype)
    counts = jax.ops.segment_sum(w, a, num_segments=centers.shape[0])
    return centers, counts, jnp.sum(d2)


def _sq_to(points, c):
    """Squared distances [n] from each point to one center [d]."""
    diff = points - c[None, :]
    return jnp.sum(diff * diff, axis=1)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _kmeans_parallel_init_device(points, mask, key, k, rounds):
    """k-means|| (Bahmani et al.) entirely on device: oversampling rounds,
    candidate weighting, and the weighted k-means++ reduction all run as
    one jitted program over fixed shapes — no host<->device churn, and the
    init overlaps the points upload instead of serializing against Lloyd.

    Fixed-shape formulation: each round Bernoulli-samples points with
    prob min(2k * d2/total, 1) (expected ~2k picks) and keeps up to
    4k of them (smallest drawn uniforms win — the draw is still a
    uniform random subset of the sampled points); candidates live in a
    [1 + rounds*4k, d] buffer with a validity mask. The final weighted
    k-means++ picks sequentially by the Gumbel-max trick, so categorical
    sampling needs no host round-trip either. Same distribution family
    as the host path, not the same RNG stream: quality equivalence (SSE)
    is the contract, asserted in tests/ops/test_trainers.py."""
    n, d = points.shape
    cap_round = min(4 * k, n)  # top_k cannot exceed the row count
    cap_t = 1 + rounds * cap_round
    maskf = mask.astype(jnp.float32)
    logmask = jnp.where(mask, 0.0, -jnp.inf)

    key, k0 = jax.random.split(key)
    i0 = jnp.argmax(jax.random.gumbel(k0, (n,)) + logmask)  # uniform valid row
    cand = jnp.zeros((cap_t, d), jnp.float32).at[0].set(points[i0])
    cvalid = jnp.zeros(cap_t, bool).at[0].set(True)
    d2 = jnp.where(mask, _sq_to(points, points[i0]), 0.0)
    # nearest-candidate id per point, tracked incrementally across rounds
    # so no final [n, cap_t] assignment pass is needed for the weights
    amin = jnp.zeros(n, jnp.int32)

    def round_body(r, carry):
        cand, cvalid, d2, amin, key = carry
        key, ku = jax.random.split(key)
        total = jnp.maximum(jnp.sum(d2), 1e-30)
        probs = jnp.minimum((2.0 * k) * d2 / total, 1.0)
        u = jax.random.uniform(ku, (n,))
        picked = (u < probs) & mask
        _, idx = jax.lax.top_k(-jnp.where(picked, u, jnp.inf), cap_round)
        newv = picked[idx]
        newpts = jnp.where(newv[:, None], points[idx], 0.0)
        base = 1 + r * cap_round
        cand = jax.lax.dynamic_update_slice(cand, newpts, (base, 0))
        cvalid = jax.lax.dynamic_update_slice(cvalid, newv, (base,))
        dn = (
            jnp.sum(points * points, axis=1, keepdims=True)
            - 2.0 * jnp.dot(points, newpts.T, preferred_element_type=jnp.float32)
            + jnp.sum(newpts * newpts, axis=1)[None, :]
        )
        dn = jnp.where(newv[None, :], dn, jnp.inf)
        dn_min = jnp.maximum(dn.min(axis=1), 0.0)
        closer = dn_min < d2
        amin = jnp.where(closer, base + jnp.argmin(dn, axis=1).astype(jnp.int32), amin)
        d2 = jnp.where(mask & closer, dn_min, d2)
        return cand, cvalid, d2, amin, key

    cand, cvalid, d2, amin, key = jax.lax.fori_loop(
        0, rounds, round_body, (cand, cvalid, d2, amin, key)
    )

    # weight candidates by how many points they attract
    w = jax.ops.segment_sum(maskf, amin, num_segments=cap_t)

    # weighted k-means++ over the candidates (Gumbel-max categorical:
    # argmax(log score + Gumbel) samples proportionally to score; an
    # already-chosen candidate has d2 = 0 -> score 0 -> never re-picked)
    key, kp0 = jax.random.split(key)
    lw = jnp.log(jnp.where(cvalid, w, 0.0))
    i0 = jnp.argmax(lw + jax.random.gumbel(kp0, (cap_t,)))
    centers = jnp.zeros((k, d), jnp.float32).at[0].set(cand[i0])
    mind2 = jnp.maximum(_sq_to(cand, cand[i0]), 0.0)

    def pp_body(i, carry):
        centers, mind2, key = carry
        key, kg = jax.random.split(key)
        score = jnp.where(cvalid, mind2 * w, 0.0)
        idx = jnp.argmax(jnp.log(score) + jax.random.gumbel(kg, (cap_t,)))
        c = cand[idx]
        centers = centers.at[i].set(c)
        mind2 = jnp.minimum(mind2, jnp.maximum(_sq_to(cand, c), 0.0))
        return centers, mind2, key

    centers, _, _ = jax.lax.fori_loop(1, k, pp_body, (centers, mind2, key))
    return centers


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _minibatch_run(points, centers0, key, iterations, batch, n_items):
    """Mini-batch k-means (Sculley 2010): each iteration assigns a random
    `batch`-point sample and moves each touched center toward the batch
    mean with a per-center learning rate 1/v_c (v_c = cumulative assigned
    count), so the steady-state cost scales with the batch size, not n.
    Returns the final centers only — callers finish with one full
    assignment pass for counts/cost."""
    n, d = points.shape
    k = centers0.shape[0]

    def body(_, carry):
        centers, v, key = carry
        key, ks = jax.random.split(key)
        idx = jax.random.randint(ks, (batch,), 0, n_items)
        xb = points[idx]
        a, _ = _assign(xb, centers, jnp.ones(batch, bool))
        sums, cnt = _centroid_sums(xb, a, jnp.ones(batch, jnp.float32), k)
        v = v + cnt
        centers = centers + (sums - cnt[:, None] * centers) / jnp.maximum(v, 1.0)[:, None]
        return centers, v, key

    centers, _, _ = jax.lax.fori_loop(
        0, iterations, body, (centers0, jnp.zeros(k, jnp.float32), key)
    )
    return centers


def train_kmeans(
    points: np.ndarray,
    k: int,
    iterations: int = 30,
    init: str = "k-means||",
    mesh: Optional[Mesh] = None,
    seed: int | None = None,
    initial_centers: np.ndarray | None = None,
    minibatch_size: int | None = None,
    init_backend: str = "auto",
) -> tuple[np.ndarray, np.ndarray, float]:
    """Returns (centers [k,d], counts [k], cost). Padded internally so the
    point rows shard evenly over the mesh. ``initial_centers`` [k, d]
    seeds Lloyd directly (warm-start from a previous generation's
    centers); a shape mismatch silently falls back to the configured
    ``init`` so a changed k or feature dim cold-starts.

    ``minibatch_size`` switches the iterations to mini-batch k-means
    (Sculley 2010; config knob oryx.ml.kmeans.minibatch-size): each
    iteration updates centers from a random sample of that many points,
    then ONE full pass produces the reported counts/cost. n at or below
    the batch size (or a mesh) runs full-batch Lloyd as before.

    ``init_backend``: "device" runs k-means|| init as one jitted program
    on the accelerator, "host" keeps the NumPy passes, "auto" = device
    except under a mesh (where points are row-sharded and the init's
    candidate set is cheapest to build on the host)."""
    import time as _time

    from oryx_tpu.common import rng as rng_mod

    points = np.asarray(points, dtype=np.float32)
    n, d = points.shape
    if n == 0:
        raise ValueError("no points")
    k = min(k, n)
    gen = np.random.default_rng(rng_mod.next_seed() if seed is None else seed)
    minibatch = minibatch_size is not None and 0 < minibatch_size < n and mesh is None
    device_init = init_backend == "device" or (init_backend == "auto" and mesh is None)

    def pick_init(pts_dev=None, n_items=None):
        # pts_dev: pre-uploaded (possibly row-padded) device points; lets
        # the device init consume the in-flight upload directly
        if initial_centers is not None:
            warm = np.asarray(initial_centers, dtype=np.float32)
            if warm.shape == (k, d):
                return warm.copy()
        if init == "random":
            return points[gen.choice(n, size=k, replace=False)]
        if device_init:
            if pts_dev is None:
                pts_dev, n_items = jnp.asarray(points), n
            pad_mask = jnp.arange(pts_dev.shape[0]) < n_items
            key = jax.random.PRNGKey(int(gen.integers(2**31)))
            return _kmeans_parallel_init_device(pts_dev, pad_mask, key, k, 2)
        return _kmeans_parallel_init(points, k, gen)

    if mesh is None and jax.default_backend() == "tpu":
        # single-device TPU: the fused Pallas sweep reads the points once
        # per iteration (no [n, k] distance matrix in HBM); huge k*d whose
        # working set would overflow VMEM falls back to the XLA path
        from oryx_tpu.ops.pallas_kmeans import (
            fits_vmem,
            lloyd_pallas,
            minibatch_lloyd_pallas,
            pad_to_block,
        )

        if fits_vmem(k, d):
            # start the H->D transfer first: jnp.asarray enqueues the copy
            # asynchronously, so the k-means|| init (device or host) runs
            # while the points stream over the link (both were serialized
            # before, and at bench scale each is a double-digit-% slice
            # of total wall)
            t_init = _time.perf_counter()
            pts_dev = jnp.asarray(pad_to_block(points))
            centers0 = np.asarray(pick_init(pts_dev, n), dtype=np.float32)
            t_iter = _time.perf_counter()
            if minibatch:
                key = jax.random.PRNGKey(int(gen.integers(2**31)))
                # every mini-batch step AND the final full pass run the
                # fused sweep kernel (one dispatch for the whole schedule)
                out = minibatch_lloyd_pallas(
                    pts_dev, centers0, iterations, int(minibatch_size), key,
                    n_items=n,
                )
            else:
                out = lloyd_pallas(pts_dev, centers0, iterations, n_items=n)
            last_phase_seconds.clear()
            last_phase_seconds.update(
                init=t_iter - t_init, iterate=_time.perf_counter() - t_iter
            )
            return out

    num_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    n_pad = pad_to_multiple(n, num_shards)
    if n_pad != n:
        points = np.concatenate([points, np.zeros((n_pad - n, d), dtype=np.float32)])
    mask = np.arange(n_pad) < n  # explicit: origin points are real data

    t_init = _time.perf_counter()
    if mesh is not None:
        centers0 = pick_init()
        rows = NamedSharding(mesh, P(DATA_AXIS, None))
        row1 = NamedSharding(mesh, P(DATA_AXIS))
        repl = NamedSharding(mesh, P())
        points_dev = jax.device_put(points, rows)
        mask_dev = jax.device_put(mask, row1)
        centers_dev = jax.device_put(np.asarray(centers0, np.float32), repl)
        t_iter = _time.perf_counter()
        centers, counts, cost = _lloyd_run(points_dev, centers_dev, mask_dev, iterations)
    else:
        pts_dev = jnp.asarray(points)
        centers0 = jnp.asarray(pick_init(pts_dev, n), dtype=jnp.float32)
        centers0.block_until_ready()
        t_iter = _time.perf_counter()
        if minibatch:
            key = jax.random.PRNGKey(int(gen.integers(2**31)))
            centers0 = _minibatch_run(
                pts_dev, centers0, key, iterations, int(minibatch_size), n
            )
            centers, counts, cost = _lloyd_run(pts_dev, centers0, mask, 0)
        else:
            centers, counts, cost = _lloyd_run(pts_dev, centers0, mask, iterations)
    centers, counts, cost = np.asarray(centers), np.asarray(counts), float(cost)
    last_phase_seconds.clear()
    last_phase_seconds.update(
        init=t_iter - t_init, iterate=_time.perf_counter() - t_iter
    )
    return centers, counts, cost


def _kmeans_parallel_init(points: np.ndarray, k: int, gen: np.random.Generator, rounds: int = 2):
    """k-means|| oversampling init then weighted k-means++ over candidates."""
    n = points.shape[0]
    centers = [points[gen.integers(n)]]
    oversample = 2 * k
    for _ in range(rounds):
        c = np.stack(centers)
        d2 = _min_sq_dists(points, c)
        total = d2.sum()
        if total <= 0:
            break
        probs = np.minimum(oversample * d2 / total, 1.0)
        picked = np.nonzero(gen.random(n) < probs)[0]
        centers.extend(points[i] for i in picked)
    cand = np.stack(centers)
    if len(cand) <= k:
        # oversampling came up short: top up with random points (keeping
        # the sampled candidates first; duplicates are harmless — Lloyd
        # leaves an empty cluster's center in place)
        extra = points[gen.choice(n, size=k, replace=n < k)]
        return np.concatenate([cand, extra])[:k]
    # weight candidates by how many points they attract, then k-means++
    assign = np.argmin(_sq_dist_matrix(points, cand), axis=1)
    weights = np.bincount(assign, minlength=len(cand)).astype(np.float64)
    return _weighted_kmeans_pp(cand, weights, k, gen)


def _weighted_kmeans_pp(cand: np.ndarray, weights: np.ndarray, k: int, gen) -> np.ndarray:
    chosen = [int(gen.choice(len(cand), p=weights / weights.sum()))]
    for _ in range(k - 1):
        d2 = _min_sq_dists(cand, cand[chosen])
        score = d2 * weights
        total = score.sum()
        if total <= 0:
            remaining = [i for i in range(len(cand)) if i not in chosen]
            chosen.append(int(gen.choice(remaining)))
            continue
        chosen.append(int(gen.choice(len(cand), p=score / total)))
    return cand[chosen]


def _sq_dist_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return (
        np.sum(a * a, axis=1, keepdims=True)
        - 2.0 * a @ b.T
        + np.sum(b * b, axis=1)[None, :]
    )


def _min_sq_dists(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.maximum(_sq_dist_matrix(a, b).min(axis=1), 0.0)


# ---------------------------------------------------------------------------
# Assignment + internal evaluation metrics
# ---------------------------------------------------------------------------


def assign_clusters(points: np.ndarray, centers: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(cluster ids, distances) for each point (Euclidean)."""
    d2 = _sq_dist_matrix(np.asarray(points, np.float64), np.asarray(centers, np.float64))
    a = np.argmin(d2, axis=1)
    return a, np.sqrt(np.maximum(d2[np.arange(len(a)), a], 0.0))


def sum_squared_error(points: np.ndarray, centers: np.ndarray) -> float:
    """SSE: lower is better (SumSquaredError.java)."""
    _, dist = assign_clusters(points, centers)
    return float(np.sum(dist**2))


def _cluster_mean_dists(points: np.ndarray, centers: np.ndarray) -> np.ndarray:
    a, dist = assign_clusters(points, centers)
    k = centers.shape[0]
    sums = np.bincount(a, weights=dist, minlength=k)
    counts = np.maximum(np.bincount(a, minlength=k), 1)
    return sums / counts


def davies_bouldin_index(points: np.ndarray, centers: np.ndarray) -> float:
    """Mean over clusters i of max_j != i (S_i + S_j) / d(c_i, c_j);
    lower is better (DaviesBouldinIndex.java)."""
    s = _cluster_mean_dists(points, centers)
    k = centers.shape[0]
    if k < 2:
        return 0.0
    cd = np.sqrt(np.maximum(_sq_dist_matrix(centers.astype(np.float64), centers.astype(np.float64)), 0))
    ratios = (s[:, None] + s[None, :]) / np.where(cd > 0, cd, np.inf)
    np.fill_diagonal(ratios, 0.0)
    return float(np.mean(ratios.max(axis=1)))


def dunn_index(points: np.ndarray, centers: np.ndarray) -> float:
    """Min centroid separation / max mean intra-cluster distance; higher
    is better (DunnIndex.java)."""
    s = _cluster_mean_dists(points, centers)
    k = centers.shape[0]
    if k < 2:
        return 0.0
    cd = np.sqrt(np.maximum(_sq_dist_matrix(centers.astype(np.float64), centers.astype(np.float64)), 0))
    cd[np.eye(k, dtype=bool)] = np.inf
    max_intra = s.max()
    if max_intra <= 0:
        return 0.0
    return float(cd.min() / max_intra)


def silhouette_coefficient(
    points: np.ndarray, centers: np.ndarray, max_sample: int = 100_000, gen=None
) -> float:
    """Mean silhouette over a sample; singleton clusters contribute 0
    (SilhouetteCoefficient.java, MAX_SAMPLE_SIZE=100000)."""
    points = np.asarray(points, dtype=np.float64)
    if gen is None:
        from oryx_tpu.common import rng as rng_mod

        gen = rng_mod.get_random()
    if len(points) > max_sample:
        points = points[gen.choice(len(points), size=max_sample, replace=False)]
    a, _ = assign_clusters(points, centers)
    k = centers.shape[0]
    total = 0.0
    count = len(points)
    if count == 0:
        return 0.0
    # Mean distance from each point to each cluster's points, computed in
    # row blocks so peak memory is O(block x largest-cluster) rather than
    # O(|cluster| x sample) — at the 100k default sample a dense per-pair
    # matrix would be tens of GB.
    by_cluster = [points[a == c] for c in range(k)]
    sizes = np.asarray([len(p) for p in by_cluster])
    block = 256
    for c in range(k):
        pts = by_cluster[c]
        if len(pts) <= 1:
            continue  # contributes 0
        for start in range(0, len(pts), block):
            blk = pts[start : start + block]
            intra = np.zeros(len(blk))
            inter = np.full(len(blk), np.inf)
            for o in range(k):
                if not sizes[o]:
                    continue
                d = np.sqrt(np.maximum(_sq_dist_matrix(blk, by_cluster[o]), 0))
                if o == c:
                    intra = d.sum(axis=1) / (sizes[c] - 1)  # exclude self (d=0)
                else:
                    inter = np.minimum(inter, d.mean(axis=1))
            valid = np.isfinite(inter)
            s = np.where(
                valid, (inter - intra) / np.maximum(np.maximum(intra, inter), 1e-300), 0.0
            )
            total += float(s.sum())
    return total / count
