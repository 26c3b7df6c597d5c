"""Pallas streaming top-N kernel, run under the interpreter on CPU.

The kernel's compiled path is exercised on the TPU by tools/chip_kernels.py
and the benchmark's cells; here the same kernel body runs in Pallas
interpret mode and is checked against a
plain numpy scan (the reference semantics: TopNConsumer.java's exact
heap-based top-N over dot scores, and CosineAverageFunction ordering).
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from oryx_tpu.ops import pallas_topn as ptn  # noqa: E402
from oryx_tpu.ops import topn as topn_ops  # noqa: E402


def _ref_topk(scores: np.ndarray, k: int):
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return idx, np.take_along_axis(scores, idx, axis=1)


def _make(n=5003, kf=24, b=4, seed=0):
    gen = np.random.default_rng(seed)
    y = gen.standard_normal((n, kf), dtype=np.float32)
    q = gen.standard_normal((b, kf), dtype=np.float32)
    return y, q


def test_streaming_topk_matches_exact_scan():
    y, q = _make()
    up = ptn.upload_streaming(y)
    idx, vals = ptn.top_k_streaming(up, q, 10, interpret=True)
    ridx, rvals = _ref_topk(q @ y.T, 10)
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_allclose(vals, rvals, atol=1e-4)


def test_streaming_topk_cosine():
    y, q = _make(seed=3)
    up = ptn.upload_streaming(y)
    idx, vals = ptn.top_k_streaming(up, q, 10, cosine=True, interpret=True)
    scores = (q @ y.T) / (
        np.linalg.norm(y, axis=1)[None, :] * np.linalg.norm(q, axis=1)[:, None]
    )
    ridx, rvals = _ref_topk(scores, 10)
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_allclose(vals, rvals, atol=1e-4)


def test_streaming_topk_single_query_and_padding():
    # n far from a BLOCK_N multiple: padded tail must never win
    y, q = _make(n=130, kf=8, b=1, seed=5)
    up = ptn.upload_streaming(y)
    assert up.mat_t.shape[1] % ptn.BLOCK_N == 0
    idx, vals = ptn.top_k_streaming(up, q[0], 130, interpret=True)
    assert idx.shape == (1, 130)
    assert set(idx[0].tolist()) == set(range(130))  # every real item, no pad ids


def test_streaming_topk_bf16_ranks_close():
    y, q = _make(n=2048, kf=32, seed=7)
    up = ptn.upload_streaming(y, dtype=jnp.bfloat16)
    idx, _ = ptn.top_k_streaming(up, q, 10, interpret=True)
    ridx, _ = _ref_topk(q @ y.T, 10)
    # bf16 scoring may swap near-ties but the candidate sets agree
    for row_got, row_ref in zip(idx, ridx):
        assert len(set(row_got.tolist()) & set(row_ref.tolist())) >= 8


def test_upload_dispatch_and_async_handle():
    y, q = _make(n=300, kf=8, seed=9)
    up = topn_ops.upload(y, streaming=False)
    idx, vals = topn_ops.top_k_scores_batch(up, q, 5)
    h = topn_ops.submit_top_k(up, q, 5)
    aidx, avals = h.result()
    np.testing.assert_array_equal(idx, aidx)
    np.testing.assert_allclose(vals, avals, atol=1e-5)
    # single-query form agrees with the batch form
    i1, v1 = topn_ops.top_k_scores(up, q[0], 5)
    np.testing.assert_array_equal(i1, aidx[0])


def test_submit_top_k_multi_matches_single():
    import numpy as np
    from oryx_tpu.ops import topn as topn_ops

    gen = np.random.default_rng(11)
    y = gen.standard_normal((3000, 16)).astype(np.float32)
    q = gen.standard_normal((300, 16)).astype(np.float32)  # ragged vs a scan group's 256
    for streaming in (False, True):
        up = topn_ops.upload(y, streaming=streaming)
        mi, mv = topn_ops.submit_top_k(up, q, 5).result()  # 2 groups of 256, the second zero-padded
        assert mi.shape == (300, 5)
        for part in (slice(0, 256), slice(256, 300)):  # one group each
            si, sv = topn_ops.submit_top_k(up, q[part], 5).result()
            np.testing.assert_array_equal(mi[part], si)
            np.testing.assert_allclose(mv[part], sv, rtol=1e-5, atol=1e-5)


def test_blocking_top_k_keeps_float32_scores_where_a_pass_downloads_bfloat16():
    """`top_k_scores_batch` answers with the scan's float32 scores on a
    bfloat16 or int8 handle, plain or streaming; a submitted pass ships
    the same ranking with bfloat16 scores (3 B a hit less on the wire)."""
    import jax.numpy as jnp
    import numpy as np

    from oryx_tpu.ops import topn as topn_ops

    gen = np.random.default_rng(17)
    y = gen.standard_normal((2000, 16)).astype(np.float32)
    q = gen.standard_normal((6, 16)).astype(np.float32)

    def on_the_bf16_grid(v):
        return np.array_equal(np.asarray(jnp.asarray(v, jnp.bfloat16).astype(jnp.float32)), v)

    for dtype, streaming in ((jnp.bfloat16, False), (jnp.bfloat16, True), (jnp.int8, True)):
        up = topn_ops.upload(y, dtype=dtype, streaming=streaming)
        bi, bv = topn_ops.top_k_scores_batch(up, q, 9)
        si, sv = topn_ops.submit_top_k(up, q, 9).result()
        np.testing.assert_array_equal(bi, si)
        assert bv.dtype == sv.dtype == np.float32
        assert on_the_bf16_grid(sv) and not on_the_bf16_grid(bv)
        np.testing.assert_allclose(sv, bv, rtol=1e-2)


def test_vector_submit_at_k200_equals_the_plain_reference():
    """No entry caps k: 200 best of a streaming handle through the public
    submit (the XLA twin here, the kernel on the TPU) and through the
    kernel under the interpreter, against the plain pair."""
    y, q = _make(n=6000, kf=16, b=5, seed=13)
    ri, rv = topn_ops.top_k_scores_batch(topn_ops.upload(y, streaming=False), q, 200)
    assert ri.shape == (5, 200)
    si, sv = topn_ops.submit_top_k(topn_ops.upload(y, streaming=True), q, 200).result()
    ki, kv = ptn.top_k_streaming(ptn.upload_streaming(y), q, 200, interpret=True)
    for idx, vals in ((si, sv), (ki, kv)):
        np.testing.assert_array_equal(idx, ri)
        np.testing.assert_allclose(vals, rv, rtol=1e-5, atol=1e-5)


def test_sharded_topk_matches_single_device():
    import numpy as np
    from oryx_tpu.ops import topn as topn_ops
    from oryx_tpu.parallel.mesh import get_mesh

    gen = np.random.default_rng(21)
    y = gen.standard_normal((5000, 12)).astype(np.float32)
    q = gen.standard_normal((9, 12)).astype(np.float32)
    mesh = get_mesh()  # 8 virtual CPU devices
    up = topn_ops.upload_sharded(y, mesh)
    si, sv = topn_ops.top_k_scores_batch(up, q, 7)
    ref = topn_ops.upload(y, streaming=False)
    ri, rv = topn_ops.top_k_scores_batch(ref, q, 7)
    np.testing.assert_allclose(sv, rv, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.sort(si, axis=1), np.sort(ri, axis=1))
    # cosine variant
    si2, sv2 = topn_ops.top_k_scores_batch(up, q, 5, cosine=True)
    ri2, rv2 = topn_ops.top_k_scores_batch(ref, q, 5, cosine=True)
    np.testing.assert_allclose(np.sort(sv2, axis=1), np.sort(rv2, axis=1), rtol=1e-5, atol=1e-5)


def test_sharded_topk_keeps_zero_vector_items():
    """Zero-embedding (cold) items rank by their true 0.0 score, exactly
    like the single-device path — padding is masked by row position, not
    by zero norms."""
    import numpy as np
    from oryx_tpu.ops import topn as topn_ops
    from oryx_tpu.parallel.mesh import get_mesh

    y = -np.abs(np.random.default_rng(3).standard_normal((20, 4))).astype(np.float32)
    y[3] = 0.0  # zero vector: dot score 0 beats all-negative scores
    q = np.ones((1, 4), dtype=np.float32)
    up = topn_ops.upload_sharded(y, get_mesh())
    si, sv = topn_ops.top_k_scores_batch(up, q, 3)
    ref = topn_ops.upload(y, streaming=False)
    ri, rv = topn_ops.top_k_scores_batch(ref, q, 3)
    np.testing.assert_array_equal(si, ri)
    assert si[0, 0] == 3 and sv[0, 0] == 0.0
    assert np.isfinite(sv).all()


def test_indexed_submit_matches_vector_submit():
    """submit_top_k_multi_indexed (int32 indices up, device-side gather)
    must return exactly the vector-submitted results for both the XLA and
    streaming handles, f32 and bf16."""
    import jax.numpy as jnp
    import numpy as np

    from oryx_tpu.ops import topn as topn_ops

    gen = np.random.default_rng(5)
    mat = gen.standard_normal((3000, 8)).astype(np.float32)
    x = gen.standard_normal((200, 8)).astype(np.float32)
    idx = gen.integers(0, 200, 70).astype(np.int32)
    x_dev = topn_ops.upload_queries(x)
    for dtype in (jnp.float32, jnp.bfloat16):
        up = topn_ops.upload(mat, dtype=dtype, streaming=False)
        i1, v1 = topn_ops.submit_top_k_multi_indexed(up, x_dev, idx, 7, scan_batch=32).result()
        i2, v2 = topn_ops.submit_top_k(up, x[idx], 7).result()  # one group
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_allclose(v1, v2, rtol=1e-5)
    ups = topn_ops.upload_streaming(mat, dtype=jnp.bfloat16)
    i3, v3 = topn_ops.submit_top_k_multi_indexed(ups, x_dev, idx, 7, scan_batch=32).result()
    i4, v4 = topn_ops.submit_top_k(ups, x[idx], 7).result()
    np.testing.assert_array_equal(i3, i4)
    np.testing.assert_allclose(v3, v4, rtol=1e-2)
    assert v1.dtype == np.float32 and v3.dtype == np.float32


def test_upload_random_device_generated_matches_host_topk():
    """upload_random builds the same handle forms as upload() without a
    host matrix; top-k through it must equal host top-k on the downloaded
    matrix, and padded columns must be zero (never winning top-k)."""
    import jax.numpy as jnp
    import numpy as np

    from oryx_tpu.ops import topn as topn_ops

    gen = np.random.default_rng(11)
    q = gen.standard_normal((4, 8)).astype(np.float32)

    # streaming (feature-major) handle, chunked device fill
    ups = topn_ops.upload_random(700, 8, dtype=jnp.float32, seed=3, streaming=True)
    assert ups.n_items == 700
    mat = np.asarray(ups.mat_t, dtype=np.float32)
    assert (mat[:, 700:] == 0).all()
    np.testing.assert_allclose(
        np.asarray(ups.norms)[0, :700], np.linalg.norm(mat[:, :700], axis=0), rtol=1e-5
    )
    idx, vals = topn_ops.top_k_scores_batch(ups, q, 5)
    scores = q @ mat[:, :700]
    expect = np.argsort(-scores, axis=1)[:, :5]
    np.testing.assert_array_equal(np.sort(idx, axis=1), np.sort(expect, axis=1))
    np.testing.assert_allclose(
        np.sort(vals, axis=1), np.sort(np.take_along_axis(scores, expect, 1), axis=1), rtol=1e-5
    )

    # plain XLA handle
    upx = topn_ops.upload_random(700, 8, dtype=jnp.float32, seed=3, streaming=False)
    matx, norms = np.asarray(upx[0]), np.asarray(upx[1])
    np.testing.assert_allclose(norms, np.linalg.norm(matx, axis=1), rtol=1e-5)
    idx2, vals2 = topn_ops.top_k_scores_batch(upx, q, 5)
    scores2 = q @ matx.T
    expect2 = np.argsort(-scores2, axis=1)[:, :5]
    np.testing.assert_array_equal(np.sort(idx2, axis=1), np.sort(expect2, axis=1))
    np.testing.assert_allclose(
        np.sort(vals2, axis=1),
        np.sort(np.take_along_axis(scores2, expect2, 1), axis=1),
        rtol=1e-5,
    )


# -- selection of the scan kernel (ISSUE 25) ----------------------------------
#
# The kernel under the interpreter against a stable NumPy top-k of the SAME
# score bits: scores come from the kernel's own ``_score_tile`` on the same
# tile slices, so values and ids must be equal exactly, ties included.


def _tile_scores(up, queries, cosine):
    """[b, n_pad] scores as the kernel's tiles hold them, padding at -inf."""
    quantized = up.scales is not None
    q = ptn._pad_queries(
        jnp.asarray(queries).astype(jnp.float32 if quantized else up.mat_t.dtype),
        up.mat_t.shape[0],
    )
    aux = ptn._fold_aux(up.norms, up.scales, cosine)
    qn = None
    if cosine:
        qf = q.astype(jnp.float32)
        qn = jnp.sqrt(jnp.sum(qf * qf, axis=1, keepdims=True))
    tiles = [
        np.asarray(
            ptn._score_tile(
                q, up.mat_t[:, at : at + ptn.SCORE_TILE], aux[:, at : at + ptn.SCORE_TILE],
                qn, cosine=cosine, quantized=quantized,
            )
        )
        for at in range(0, up.mat_t.shape[1], ptn.SCORE_TILE)
    ]
    scores = np.concatenate(tiles, axis=1)
    scores[:, up.n_items :] = -np.inf
    return scores, q, qn


def _normal(n, kf, b, seed):
    gen = np.random.default_rng(seed)
    return gen.standard_normal((n, kf), dtype=np.float32), gen.standard_normal((b, kf), dtype=np.float32)


def _integers(n, kf, b, seed):
    # small integer factors: scores are small integers, so thousands tie
    gen = np.random.default_rng(seed)
    return (
        gen.integers(-2, 3, (n, kf)).astype(np.float32),
        gen.integers(-2, 3, (b, kf)).astype(np.float32),
    )


def _by_item_id(sign):
    def make(n, kf, b, seed):
        # score = sign * (row + 1) * item id: ascending, every tile enters k
        # items; descending, only the first tile does
        y = np.zeros((n, kf), np.float32)
        y[:, 0] = sign * np.arange(n, dtype=np.float32)
        q = np.zeros((b, kf), np.float32)
        q[:, 0] = 1.0 + np.arange(b, dtype=np.float32)
        return y, q

    return make


def _all_equal(n, kf, b, seed):
    return np.ones((n, kf), np.float32), np.ones((b, kf), np.float32)


SELECTION_CASES = {
    # name: (factors, items, features, b, k, item dtype, cosine)
    "random-b8-k32": (_normal, 20_000, 24, 8, 32, "float32", False),
    "random-b16-k16": (_normal, 20_000, 24, 16, 16, "float32", False),
    "random-b3-k128": (_normal, 20_000, 24, 3, 128, "float32", False),
    "random-b64-k32": (_normal, 20_000, 24, 64, 32, "float32", False),
    # two vregs of state a row: no entry caps k
    "ties-b8-k256": (_integers, 20_000, 6, 8, 256, "float32", False),
    "ties-b8-k32": (_integers, 20_000, 6, 8, 32, "float32", False),
    "ties-b16-k16": (_integers, 20_000, 6, 16, 16, "float32", False),
    "ties-b3-k128": (_integers, 20_000, 6, 3, 128, "float32", False),
    "ascending-b8-k32": (_by_item_id(1.0), 20_000, 8, 8, 32, "float32", False),
    "descending-b8-k32": (_by_item_id(-1.0), 20_000, 8, 8, 32, "float32", False),
    "all-equal-b16-k16": (_all_equal, 20_000, 8, 16, 16, "float32", False),
    "one-tile-edge-b8-k32": (_integers, 4097, 6, 8, 32, "float32", False),
    "below-k-b16-k16": (_integers, 20, 6, 16, 16, "float32", False),
    "fewer-than-k-b8-k32": (_normal, 20, 8, 8, 32, "float32", False),
    "cosine-b8-k32": (_normal, 20_000, 24, 8, 32, "float32", True),
    "cosine-ties-b16-k16": (_integers, 20_000, 6, 16, 16, "float32", True),
    "bfloat16-b8-k32": (_normal, 20_000, 24, 8, 32, "bfloat16", False),
    "int8-rescore-b8-k32": (_normal, 20_000, 24, 8, 32, "int8", False),
    "int8-rescore-b16-k16": (_normal, 20_000, 24, 16, 16, "int8", False),
    # more rows than one scan group holds: 256 + 44 (padded to 256) rows
    "two-groups-b300-k16": (_integers, 9_000, 6, 300, 16, "float32", False),
}


@pytest.mark.parametrize("case", SELECTION_CASES)
def test_scratch_kernel_selection_is_a_stable_topk(case):
    make, n, kf, b, k, dtype, cosine = SELECTION_CASES[case]
    y, queries = make(n, kf, b, seed=len(case))
    up = ptn.upload_streaming(y, dtype=jnp.dtype(dtype))
    k = min(k, n)  # as every public entry clamps it

    def scan(k, resid, resid_scales):
        vals, idxs = ptn.split_hits(ptn._streaming_topk_multi(
            up.mat_t, up.norms, up.scales, resid, resid_scales,
            jnp.asarray(ptn.group_rows(queries)),
            k=k, n_items=n, cosine=cosine, interpret=True,
        ))
        return np.asarray(vals).reshape(-1, k)[:b], np.asarray(idxs).reshape(-1, k)[:b]

    scores, q, qn = _tile_scores(up, queries, cosine)
    # an int8 scan keeps 4k candidates (at most 128) for the residual rescore:
    # the kernel's own stage is the scan of that many with no residual plane
    m = ptn._scan_k(k, n, up.resid)
    assert m == k or dtype == "int8"
    vals, idxs = scan(m, None, None)
    ridx, rvals = _ref_topk(scores, m)
    np.testing.assert_array_equal(idxs, ridx)
    np.testing.assert_array_equal(vals, rvals)
    assert idxs.max() < n and np.isfinite(vals).all()
    if dtype == "int8":
        # the rescore is XLA's, outside the kernel: fused differently in the
        # scan program than called alone, so its sums agree to a rounding
        fvals, fidxs = scan(k, up.resid, up.resid_scales)
        rvals, ridx = ptn._rescore_topk(
            jnp.asarray(rvals), jnp.asarray(ridx.astype(np.int32)), q, qn,
            up.resid, up.resid_scales, up.norms, k=k, cosine=cosine,
        )
        np.testing.assert_array_equal(fidxs, np.asarray(ridx))
        np.testing.assert_allclose(fvals, np.asarray(rvals), rtol=1e-6)


def _replay_rounds(scores, k):
    """The rule, replayed tile by tile on the host: a tile is gated when
    any row's best beats that row's running k-th best; a round enters each
    row's best remaining score while it is strictly above the k-th best;
    the tile's rounds are those of the row that enters most."""
    b = scores.shape[0]
    state = [[-np.inf] * k for _ in range(b)]  # descending
    gated = rounds = 0
    for at in range(0, scores.shape[1], ptn.SCORE_TILE):
        tile = scores[:, at : at + ptn.SCORE_TILE]
        if not any(tile[r].max() > state[r][-1] for r in range(b)):
            continue
        gated += 1
        most = 0
        for r in range(b):
            entered = 0
            for s in np.sort(tile[r])[::-1][:k]:
                if not s > state[r][-1]:
                    break
                state[r] = sorted(state[r] + [s], reverse=True)[:k]
                entered += 1
            most = max(most, entered)
        rounds += most
    return gated, rounds


@pytest.mark.parametrize(
    "case", ["random-b8-k32", "ties-b16-k16", "ascending-b8-k32", "descending-b8-k32"]
)
def test_counted_rounds_are_the_rule_replayed(case):
    make, n, kf, b, k, dtype, cosine = SELECTION_CASES[case]
    # the random order needs enough tiles for the first ones, which enter
    # k each, not to count: 245 score tiles there, 15 elsewhere
    n = 1_000_000 if case.startswith("random") else 60_000
    y, queries = make(n, kf, b, seed=7)
    up = ptn.upload_streaming(y)
    args = (up.mat_t, up.norms, None, None, None, jnp.asarray(queries))
    kwargs = dict(k=k, n_items=n, cosine=cosine, interpret=True)
    vals, idxs, counts = ptn._streaming_topk_impl(*args, count_rounds=True, **kwargs)
    plain = ptn._streaming_topk_impl(*args, **kwargs)
    assert len(plain) == 2  # flag off: the two outputs there were before
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(vals))
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(idxs))
    gated, rounds = (int(c) for c in np.asarray(counts)[0])
    scores, _, _ = _tile_scores(up, queries, cosine)
    assert (gated, rounds) == _replay_rounds(scores, k)
    tiles = -(-n // ptn.SCORE_TILE)
    assert 1 <= gated <= tiles and gated <= rounds <= gated * k
    if case.startswith("random"):
        # what ISSUE 25 is about: the parent ran 2k rounds a gated tile
        assert rounds < gated * 2 * k / 10
    if case.startswith("ascending"):
        assert (gated, rounds) == (tiles, tiles * k)  # every tile enters k
    if case.startswith("descending"):
        assert (gated, rounds) == (1, k)  # the first tile holds the answer


def test_counting_flag_off_leaves_the_kernel_two_outputs():
    import jax

    y, queries = _normal(5000, 8, 8, seed=1)
    up = ptn.upload_streaming(y)

    def kernel_outputs(**flag):
        jaxpr = jax.make_jaxpr(
            lambda q: ptn._streaming_topk_impl(
                up.mat_t, up.norms, None, None, None, q,
                k=16, n_items=5000, cosine=False, interpret=False, **flag,
            )
        )(jnp.asarray(queries))
        (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
        return [(v.aval.shape, str(v.aval.dtype)) for v in call.outvars], len(jaxpr.out_avals)

    assert kernel_outputs() == ([((8, 16), "float32"), ((8, 16), "int32")], 2)
    assert kernel_outputs(count_rounds=True) == (
        [((8, 16), "float32"), ((8, 16), "int32"), ((1, 2), "int32")], 3,
    )
    with pytest.raises(ValueError, match="at most 256 rows"):  # more rows are more groups
        ptn._streaming_topk_impl(
            up.mat_t, up.norms, None, None, None, jnp.zeros((ptn.MAX_GROUP_ROWS + 8, 8)),
            k=16, n_items=5000, cosine=False, interpret=True,
        )


# -- host layout helpers (PR 26): the transposing copy and the norms run in
# bands of rows on a few threads and must equal the one-thread numpy forms --


@pytest.mark.parametrize(
    "n,k,cols,dtype,height",
    [
        (5, 3, 8, np.float32, None),
        (70_001, 7, 81_920, np.float32, None),  # two bands, padding past n
        (131_072, 4, 131_072, np.float32, None),  # bands end on n, no padding
        (70_001, 7, 81_920, jnp.bfloat16, None),
        (66_000, 50, 81_920, np.int8, 64),  # int8 planes pad the features too
    ],
)
def test_feature_major_equals_the_plain_transposing_copy(n, k, cols, dtype, height):
    from oryx_tpu.ops.pallas_topn import feature_major

    gen = np.random.default_rng(n)
    rows = (gen.standard_normal((n, k)) * 40).astype(np.int8 if dtype is np.int8 else np.float32)
    want = np.zeros((height or k, cols), dtype=dtype)
    want[:k, :n] = rows.T
    got = feature_major(rows, cols, dtype, height)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


@pytest.mark.parametrize("n,k", [(1, 3), (65_536, 5), (150_000, 250)])
def test_row_norms_equal_numpy_bit_for_bit(n, k):
    from oryx_tpu.ops.pallas_topn import row_norms

    rows = np.random.default_rng(k).standard_normal((n, k)).astype(np.float32)
    got = row_norms(rows)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, np.linalg.norm(rows, axis=1))


# -- the split layout: a float32 matrix streamed at its logical feature width -----------------

# features -> (main plane rows, tail plane rows or None, rows the device stores an item)
SPLIT_LAYOUT = {
    1: (1, None, 1), 2: (2, None, 2), 4: (4, None, 4),  # no whole tile before the tail
    30: (30, None, 32),  # 6 rows over: a 4-row tail cannot hold them, padded as ever
    48: (48, None, 48),
    50: (48, 2, 50), 100: (96, 4, 100), 250: (248, 2, 250),
    51: (48, 4, 52),  # 3 rows ride in a 4-row tail
    9: (8, 1, 9),
}


def _small_ints(shape, seed, low=1, high=3):
    """Small whole numbers of either sign, none zero, as float32: every
    dot product is exact in any order of summation, so equal scores are
    equal bit for bit (and there are many), no sum is a negative zero
    (which ``lax.top_k`` ranks below zero and the kernel does not), and
    the ids of two exact scans must agree one for one."""
    gen = np.random.default_rng(seed)
    return (gen.integers(low, high, shape) * gen.choice([-1, 1], shape)).astype(np.float32)


@pytest.mark.parametrize("features", sorted(SPLIT_LAYOUT))
def test_float32_upload_stores_the_logical_rows_where_the_rule_engages(features):
    from oryx_tpu.common.metrics import registry as metrics

    main, tail, stored = SPLIT_LAYOUT[features]
    y = _small_ints((300, features), features)
    up = ptn.upload_streaming(y)
    assert up.mat_t.shape == (main, ptn.BLOCK_N) and up.mat_t.dtype == jnp.float32
    assert (None if up.tail is None else up.tail.shape) == (tail and (tail, ptn.BLOCK_N))
    assert ptn.tail_rows(features, np.float32) == (tail or 0)
    assert up.num_features == features and ptn.stored_feature_rows(up) == stored
    snap = metrics.snapshot()
    assert snap["serving.scan.feature-rows.logical"]["value"] == features
    assert snap["serving.scan.feature-rows.stored"]["value"] == stored
    # the planes hold the matrix: main rows first, the tail's after, zeros beyond
    planes = [np.asarray(up.mat_t)] + ([] if tail is None else [np.asarray(up.tail)])
    whole = np.concatenate(planes)
    np.testing.assert_array_equal(whole[:features, :300], y.T)
    assert not whole[features:].any() and not whole[:, 300:].any()


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("features", [50, 250])
def test_bfloat16_and_int8_keep_one_plane(features, dtype):
    """Tiles of 16 and 32 rows, no tail: the layout these handles had."""
    y = _small_ints((300, features), 3)
    up = ptn.upload_streaming(y, dtype=jnp.dtype(dtype))
    assert up.tail is None and ptn.tail_rows(features, jnp.dtype(dtype)) == 0
    rows = ptn._ceil_to(features, 32) if dtype == "int8" else features
    assert up.mat_t.shape == (rows, ptn.BLOCK_N) and up.num_features == features
    assert ptn.stored_feature_rows(up) == ptn._ceil_to(features, 16 if dtype == "bfloat16" else 32)


@pytest.mark.parametrize("backend", ["kernel", "xla-twin"])
@pytest.mark.parametrize("metric", ["dot", "cosine"])
@pytest.mark.parametrize("features", [1, 2, 4, 30, 48, 50, 100, 250])
def test_split_layout_scan_equals_the_plain_scan(features, metric, backend):
    """Ids equal one for one, ties in item-id order, against
    ``_plain_topk_groups`` on the same factors and norms: through the
    interpreter's kernel and through the XLA twin, indexed and by vector,
    over two grid steps with the second partly padding."""
    n, b, k = 20_000, 8, 24
    y, x = _small_ints((n, features), features), _small_ints((64, features), 1000 + features)
    cosine = metric == "cosine"
    up = ptn.upload_streaming(y)
    assert up.mat_t.shape[1] == 2 * ptn.BLOCK_N
    rows = np.arange(b, dtype=np.int32) * 3
    x_dev = topn_ops.upload_queries(x)
    norms = up.norms[0, :n]
    rv, ri = ptn.split_hits(topn_ops._plain_topk_groups(
        jnp.asarray(y), norms, x_dev, jnp.asarray(rows[None, :]), k, cosine, None
    ))
    interpret = True if backend == "kernel" else None
    vals, idxs = ptn.split_hits(ptn.scan_groups(
        up, jnp.asarray(rows[None, :]), k, cosine=cosine, interpret=interpret, x_dev=x_dev
    ))
    np.testing.assert_array_equal(np.asarray(idxs), np.asarray(ri))
    if cosine:
        np.testing.assert_allclose(np.asarray(vals), np.asarray(rv), rtol=0, atol=3e-7)
    else:
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(rv))
        ties = np.asarray(vals)[0, :, 1:] == np.asarray(vals)[0, :, :-1]
        assert ties.any()  # the data has equal scores, and they came out lowest id first
        assert (np.diff(np.asarray(idxs)[0], axis=1)[ties] > 0).all()
    by_vector = ptn.top_k_streaming(up, x[rows], k, cosine=cosine, interpret=interpret)
    np.testing.assert_array_equal(by_vector[0], np.asarray(idxs)[0])


@pytest.mark.parametrize("layout", ["one-device", "sharded"])
@pytest.mark.parametrize("features", [50, 51, 250, 48])
def test_a_row_update_writes_both_planes(features, layout):
    """Updated and appended rows leave the handle plane for plane what a
    fresh upload of the updated matrix holds, and are served."""
    from oryx_tpu.parallel.mesh import get_mesh

    n = 4001
    y = _small_ints((n, features), 7)
    fresh = np.abs(_small_ints((5, features), 8, low=3, high=6))  # beat every old row
    rows = np.array([0, 1999, 2000, 4000, 4001], dtype=np.int32)  # the last one appends
    after = np.concatenate([y, np.zeros((1, features), np.float32)])
    after[rows] = fresh

    def upload(mat):
        if layout == "sharded":
            return topn_ops.upload_sharded(mat, get_mesh())
        return ptn.upload_streaming(mat)

    # in place: plane for plane a fresh upload (an append would cut new shards)
    got, want = topn_ops.update_rows(upload(y), rows[:4], fresh[:4]), upload(after[:n])
    assert got.n_items == n and got.features == want.features
    assert (got.tail is None) == (want.tail is None) == (features % 8 == 0)
    for name in ("mat_t", "tail", "norms"):
        a, b = getattr(got, name), getattr(want, name)
        if a is not None or b is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    got = topn_ops.update_rows(got, rows[4:], fresh[4:], n_items=n + 1)
    assert got.n_items == n + 1
    q = np.ones((1, features), np.float32)
    idx, vals = topn_ops.top_k_scores_batch(got, q, 5)
    ridx, rvals = _ref_topk(q @ after.T, 5)
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_array_equal(vals, rvals)
    assert set(idx[0]) == set(rows.tolist())


@pytest.mark.parametrize("features", [50, 250])
def test_sharded_split_layout_equals_one_device(features):
    """Every shard holds the same two planes the one-device handle would
    hold of its rows, and the merged answer is the one-device answer."""
    from oryx_tpu.parallel.mesh import get_mesh

    y, q = _small_ints((9001, features), 11), _small_ints((6, features), 12)
    mesh = get_mesh()
    up = topn_ops.upload_sharded(y, mesh)
    d = mesh.devices.size
    main, tail, stored = SPLIT_LAYOUT[features]
    assert up.mat_t.shape == (main, d * up.cols) and up.tail.shape == (tail, d * up.cols)
    assert up.features == features and f", {features})" in topn_ops.sharded_layout(up)
    assert {s.device for s in up.tail.addressable_shards} == set(mesh.devices.flat)
    for metric in (False, True):
        si, sv = topn_ops.top_k_scores_batch(up, q, 12, cosine=metric)
        oi, ov = topn_ops.top_k_scores_batch(ptn.upload_streaming(y), q, 12, cosine=metric)
        np.testing.assert_array_equal(si, oi)
        np.testing.assert_array_equal(sv, ov)


def test_upload_random_splits_the_same_values():
    up = topn_ops.upload_random(3000, 50, jnp.float32, seed=5, streaming=True)
    whole, _ = topn_ops._gen_streaming_random(
        __import__("jax").random.PRNGKey(5), 50, ptn.BLOCK_N, 3000, jnp.float32
    )
    assert up.mat_t.shape == (48, ptn.BLOCK_N) and up.tail.shape == (2, ptn.BLOCK_N)
    assert up.num_features == 50
    np.testing.assert_array_equal(np.concatenate([up.mat_t, up.tail]), np.asarray(whole))
