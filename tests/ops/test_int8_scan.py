"""Quantized serving-scan parity: int8 two-plane recall against exact
float32, requantize round-trips through speed-layer fold-ins, and
sharded-scan equivalence. Tier-1 `-m scan` suite — everything here runs
on the CPU XLA twin of the blocked scan in well under a minute.

Recall checks are tie-tolerant: a returned item counts as a hit when its
TRUE (float32) score reaches the true k-th best minus 1e-5. Quantization
may legitimately reorder items whose true scores are closer than its
resolution; the adversarial test below builds exactly that cohort and
asserts the scan still never drops a clear winner.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from oryx_tpu.ops import pallas_topn as pt
from oryx_tpu.ops import topn as topn_ops

pytestmark = pytest.mark.scan

K = 10
TIE_TOL = 1e-5


def _recall(mat, queries, idx, k=K, tol=TIE_TOL):
    """Tie-tolerant recall@k of returned indices vs the exact ranking."""
    ref = queries @ mat.T
    hits = 0
    for r in range(len(queries)):
        kth = np.partition(ref[r], -k)[-k]
        hits += int(np.sum(ref[r][np.asarray(idx[r])] >= kth - tol))
    return hits / (len(queries) * k)


def _random_case(n=50_000, f=48, b=16, seed=0):
    gen = np.random.default_rng(seed)
    mat = gen.standard_normal((n, f)).astype(np.float32)
    queries = gen.standard_normal((b, f)).astype(np.float32)
    return mat, queries


def test_int8_recall_seeded_random():
    mat, queries = _random_case()
    up = pt.upload_streaming(mat, dtype=jnp.int8)
    _vals, idx = pt.top_k_streaming_device(up, queries, k=K)
    assert _recall(mat, queries, idx) >= 0.99


def test_int8_recall_cosine():
    mat, queries = _random_case(seed=1)
    up = pt.upload_streaming(mat, dtype=jnp.int8)
    _vals, idx = pt.top_k_streaming_device(up, queries, k=K, cosine=True)
    norms = np.linalg.norm(mat, axis=1)
    ref = (queries @ mat.T) / (norms[None, :] * np.linalg.norm(queries, axis=1)[:, None])
    hits = 0
    for r in range(len(queries)):
        kth = np.partition(ref[r], -K)[-K]
        hits += int(np.sum(ref[r][np.asarray(idx[r])] >= kth - 1e-7))
    assert hits / (len(queries) * K) >= 0.99


def test_int8_recall_adversarial_near_ties():
    """A cohort of items whose true scores tie within 1e-7 — far inside
    int8 resolution, so quantization reorders them freely — plus a band
    of clear winners that beat the cohort by a wide margin. The scan must
    return only winners and tied-cohort members (tie-tolerant hit), and
    every one of the clear winners must survive quantization."""
    gen = np.random.default_rng(7)
    n, f, b = 20_000, 32, 8
    base = gen.standard_normal(f).astype(np.float32)
    base /= np.linalg.norm(base)
    # near-tie cohort: every row is the same direction, so true scores
    # tie within ~1e-6 — far inside both int8 resolution AND the 1e-5
    # tie tolerance, so ANY ordering of the cohort is a legitimate answer
    mat = np.tile(base, (n, 1)).astype(np.float32)
    # orthogonal jitter (never changes the score against `base`-aligned
    # queries) so rows are not bit-identical and quantize independently
    jitter = gen.standard_normal((n, f)).astype(np.float32) * 1e-3
    jitter -= np.outer(jitter @ base, base)
    mat += jitter
    winners = gen.choice(n, size=2 * K, replace=False)
    mat[winners] *= 1.5  # clear margin: ~50% higher score
    queries = np.tile(base, (b, 1)).astype(np.float32)
    queries += gen.standard_normal((b, f)).astype(np.float32) * 1e-4

    up = pt.upload_streaming(mat, dtype=jnp.int8)
    _vals, idx = pt.top_k_streaming_device(up, queries, k=K)
    assert _recall(mat, queries, idx) >= 0.99
    # every returned item must come from the winner band: the margin is
    # orders of magnitude beyond quantization error
    for r in range(b):
        assert set(np.asarray(idx[r])) <= set(winners.tolist()), (
            f"row {r}: quantized scan leaked a non-winner into the top-{K}"
        )


def test_requantize_round_trip_after_update_rows():
    """Speed-layer fold-in path: update_rows on an int8 handle requantizes
    exactly the touched rows, bit-identically to a fresh upload of the
    updated matrix (host-side quantization in both paths — no device FMA
    drift)."""
    mat, _ = _random_case(n=4_000, f=24, seed=3)
    gen = np.random.default_rng(4)
    rows = gen.choice(len(mat), size=200, replace=False).astype(np.int32)
    vals = gen.standard_normal((200, 24)).astype(np.float32)

    up = topn_ops.update_rows(pt.upload_streaming(mat, dtype=jnp.int8), rows, vals)
    mat2 = mat.copy()
    mat2[rows] = vals
    fresh = pt.upload_streaming(mat2, dtype=jnp.int8)
    for name in ("mat_t", "norms", "scales", "resid", "resid_scales"):
        np.testing.assert_array_equal(
            np.asarray(getattr(up, name)),
            np.asarray(getattr(fresh, name)),
            err_msg=f"update_rows round-trip diverged on {name}",
        )


def test_update_rows_results_visible_in_scan():
    mat, queries = _random_case(n=8_000, f=24, b=4, seed=5)
    up = pt.upload_streaming(mat, dtype=jnp.int8)
    # boost a handful of rows so they MUST take over the top-k
    gen = np.random.default_rng(6)
    rows = gen.choice(len(mat), size=K, replace=False).astype(np.int32)
    vals = queries[0][None, :] * 50.0 + gen.standard_normal((K, 24)).astype(np.float32)
    up = topn_ops.update_rows(up, rows, vals.astype(np.float32))
    _vals, idx = pt.top_k_streaming_device(up, queries[:1], k=K)
    assert set(np.asarray(idx[0])) == set(rows.tolist())


def test_sharded_scan_matches_streaming():
    """Row-sharded int8 scan (full two-plane scoring per shard) agrees
    with the single-device streaming scan: same tie-tolerant recall, and
    identical top-k SETS wherever the true scores are distinct."""
    from oryx_tpu.parallel.mesh import get_mesh

    mat, queries = _random_case(n=30_000, f=48, b=8, seed=8)
    up_s = topn_ops.upload_sharded(mat, get_mesh(), dtype=jnp.int8)
    idx_sh, _vals_sh = topn_ops.top_k_scores_batch(up_s, queries, k=K)
    assert _recall(mat, queries, idx_sh) >= 0.99

    up = pt.upload_streaming(mat, dtype=jnp.int8)
    _vals_st, idx_st = pt.top_k_streaming_device(up, queries, k=K)
    ref = queries @ mat.T
    for r in range(len(queries)):
        kth = np.partition(ref[r], -K)[-K]
        # compare sets only over items strictly above the tie band
        clear = {i for i in np.asarray(idx_sh[r]).tolist() if ref[r][i] > kth + TIE_TOL}
        assert clear <= set(np.asarray(idx_st[r]).tolist())


def test_f32_scan_stays_exact():
    """The non-quantized XLA scan path keeps exact parity with a stable
    numpy argsort — the int8 machinery must not disturb it."""
    mat, queries = _random_case(n=20_000, f=32, b=8, seed=9)
    up = pt.upload_streaming(mat, dtype=jnp.float32)
    _vals, idx = pt.top_k_streaming_device(up, queries, k=K)
    ref = queries @ mat.T
    expect = np.argsort(-ref, axis=1, kind="stable")[:, :K]
    np.testing.assert_array_equal(np.asarray(idx), expect)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla-twin", "kernel"])
def test_large_k_int8_keeps_its_recall(interpret):
    """k past the oversample's cap of 128 runs the same scan as any k
    (two vregs of state a row in the kernel) — overlap with exact f32
    stays >= 0.99."""
    mat, queries = _random_case(n=5_000, f=24, b=4, seed=10)
    k = pt.OVERSAMPLE_CAP + 16
    up = pt.upload_streaming(mat, dtype=jnp.int8)
    _vals, idx = pt.top_k_streaming_device(up, queries, k=k, interpret=interpret)
    assert _recall(mat, queries, idx, k=k) >= 0.99
