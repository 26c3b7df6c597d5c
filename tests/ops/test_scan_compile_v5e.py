"""Compile rehearsals for the TPU v5e (no chip needed) of the scan programs
that no benchmark cell compiles: tests/benchmark/test_bench_compile_v5e.py
holds the float32 programs of the cells (batch buckets 8-128, k bucket 32);
here are the other item dtypes (bfloat16; int8 two-plane, whose kernel keeps
128 candidates for the rescore), the k buckets 16, 128 and 256, and the
widest scan group (256 rows), at 50 and at 250 features; and the VECTOR
submit's program as it is served (`_streaming_topk_multi`: the query block
an operand, not rows of a staged matrix; `/similarity`, anonymous users,
several users at once), cosine and dot, at the k bucket 16 and, for the
anonymous-visitor cell's baskets of 7-8 items, dot at 32; and the Gram
program of the same device matrix (`ops/gram.py` `oryx_gram`, the fold-in's
`YtY`). What the chip's
compiler would refuse (VMEM, tiling, the lane roll of the running top-k)
is refused here. A compile that passes is not a
chip run and says nothing about time.

The topology is described inside a module fixture, never at import
(on-chip-measurement guide, section 2)."""

import functools

import pytest

# (items, features) of the two benchmark configurations
SHAPES = {50: 20_000_000, 250: 5_000_000}

CASES = {
    # name: (features, item dtype, batch rows, k)
    "int8-two-plane-250f": (250, "int8", 8, 32),
    "int8-two-plane-50f": (50, "int8", 16, 10),
    "bfloat16-250f": (250, "bfloat16", 8, 32),
    "bfloat16-50f": (50, "bfloat16", 32, 32),
    "k16-50f": (50, "float32", 8, 16),
    "k16-250f": (250, "float32", 16, 16),
    "k128-50f": (50, "float32", 8, 128),
    "k128-250f": (250, "float32", 8, 128),
    "k256-50f": (50, "float32", 8, 256),  # a howMany over 128 through the batcher
    "b256-50f": (50, "float32", 256, 32),
    "b256-250f": (250, "float32", 256, 32),
}

# The float32 programs as they are SERVED since PR 30: the item matrix
# split into a main plane of whole sublane tiles and a tail plane of
# `features % 8` rows (pallas_topn.tail_rows). tests/benchmark's compile
# tests lower the un-split operand form, which the same kernel still takes.
# name: (features, batch rows, cosine)
SPLIT_CASES = {
    "split-50f-b8": (50, 8, False),
    "split-50f-b32": (50, 32, False),
    "split-50f-b128": (50, 128, False),
    "split-250f-b8": (250, 8, False),
    "split-250f-b32": (250, 32, False),
    "split-250f-b128": (250, 128, False),
    "split-50f-cosine": (50, 16, True),
    "split-250f-b256-cosine": (250, 256, True),
}

# The VECTOR submit's program as it is served (`_streaming_topk_multi`: a
# [1, b, features] float32 block uploaded with the pass, split operands):
# what `/similarity` (cosine, k bucket 16 at howMany=10) and every request
# whose query is not a staged row run. name: (features, batch rows, cosine)
VECTOR_CASES = {
    f"vector-{f}f-b{b}-{'cosine' if cosine else 'dot'}": (f, b, cosine)
    for f in (250, 50) for b in (8, 128) for cosine in (True, False)
}


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(sharding, dims, dtype):
    import jax

    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


@pytest.mark.parametrize("case", CASES)
def test_scan_program_compiles_for_the_v5e(case, one_chip, no_persistent_cache):
    import jax.numpy as jnp

    from oryx_tpu.ops import pallas_topn

    features, dtype, batch, k = CASES[case]
    items = SHAPES[features]
    n_pad = pallas_topn._ceil_to(items, pallas_topn.BLOCK_N)

    shape = functools.partial(_shape, one_chip)
    row = shape((1, n_pad), jnp.float32)
    scales = resid = resid_scales = None
    stored = features
    if dtype == "int8":
        stored = pallas_topn._ceil_to(features, pallas_topn._INT8_FEAT_MULTIPLE)
        scales, resid, resid_scales = row, shape((stored, n_pad), jnp.int8), row
    lowered = pallas_topn._streaming_topk_multi_indexed.lower(
        shape((stored, n_pad), jnp.dtype(dtype)), row, scales, resid, resid_scales,
        shape((4096, features), jnp.float32), shape((1, batch), jnp.int32),
        k=k, n_items=items, cosine=False, interpret=False, download_dtype=None,
    )
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    assert "tpu_custom_call" in compiled.as_text()
    assert "oryx_topn_scan" in compiled.as_text()  # the one scan kernel
    hits = lowered.out_info  # ONE array a pass: the scores' bits, then the ids
    assert hits.shape == (1, batch, 2 * k) and hits.dtype == jnp.int32
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("case", SPLIT_CASES)
def test_served_split_scan_program_compiles_for_the_v5e(case, one_chip, no_persistent_cache):
    """Main plane + tail plane as two streamed operands of the ONE kernel,
    and the device holds the matrix at its logical width: the arguments
    are smaller than the un-split program's by the padding rows."""
    import jax.numpy as jnp

    from oryx_tpu.ops import pallas_topn

    features, batch, cosine = SPLIT_CASES[case]
    items = SHAPES[features]
    n_pad = pallas_topn._ceil_to(items, pallas_topn.BLOCK_N)
    tail = pallas_topn.tail_rows(features, jnp.float32)
    assert tail == 2

    shape = functools.partial(_shape, one_chip)
    row, users, rows = shape((1, n_pad), jnp.float32), shape((4096, features), jnp.float32), shape((1, batch), jnp.int32)
    static = dict(k=32, n_items=items, cosine=cosine, interpret=False, download_dtype=None)
    lowered = pallas_topn._streaming_topk_multi_indexed.lower(
        shape((features - tail, n_pad), jnp.float32), row, None, None, None, users, rows,
        tail=shape((tail, n_pad), jnp.float32), **static,
    )
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1  # one kernel, the named one
    assert "%oryx_topn_scan" in text
    assert f"f32[{tail},{n_pad}]{{1,0:T({tail},128)}}" in text  # the tail is stored 2 rows high
    hits = lowered.out_info  # ONE array a pass: the scores' bits, then the ids
    assert hits.shape == (1, batch, 2 * 32) and hits.dtype == jnp.int32
    stored = compiled.memory_analysis().argument_size_in_bytes
    whole = pallas_topn._streaming_topk_multi_indexed.lower(
        shape((features, n_pad), jnp.float32), row, None, None, None, users, rows, **static
    ).compile().memory_analysis().argument_size_in_bytes
    padding = (pallas_topn._ceil_to(features, 8) - features) * n_pad * 4
    assert stored == whole - padding


@pytest.mark.parametrize("case", VECTOR_CASES)
def test_served_vector_scan_program_compiles_for_the_v5e(case, one_chip, no_persistent_cache):
    """The same kernel behind the other submit kind: the queries arrive as
    an operand of the program, and the cosine variant divides by the norms
    row that the dot variant streams and ignores."""
    import jax.numpy as jnp

    from oryx_tpu.ops import pallas_topn

    features, batch, cosine = VECTOR_CASES[case]
    items, k = SHAPES[features], 16
    n_pad = pallas_topn._ceil_to(items, pallas_topn.BLOCK_N)
    tail = pallas_topn.tail_rows(features, jnp.float32)

    shape = functools.partial(_shape, one_chip)
    lowered = pallas_topn._streaming_topk_multi.lower(
        shape((features - tail, n_pad), jnp.float32), shape((1, n_pad), jnp.float32),
        None, None, None, shape((1, batch, features), jnp.float32),
        k=k, n_items=items, cosine=cosine, interpret=False, download_dtype=None,
        tail=shape((tail, n_pad), jnp.float32),
    )
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%oryx_topn_scan" in text
    hits = lowered.out_info  # ONE array a pass: the scores' bits, then the ids
    assert hits.shape == (1, batch, 2 * k) and hits.dtype == jnp.int32
    mem = compiled.memory_analysis()
    # the matrix at its logical width, its norms, and the query block (which
    # the device tiles to whole 128-lane columns); no [b, n] scores anywhere
    planes = (features * n_pad + n_pad) * 4
    block = batch * pallas_topn._ceil_to(features, 128) * 4
    assert planes < mem.argument_size_in_bytes <= planes + block
    assert mem.temp_size_in_bytes < 64 * 2**20


def test_served_split_sharded_program_compiles_for_a_v5e_host(no_persistent_cache):
    """The four-chip cell's program in its served operand form: each chip
    its `[248, cols]` main and `[2, cols]` tail plane."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from oryx_tpu.ops import pallas_topn, topn

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    d, f, batch = 4, 250, 32
    cols = pallas_topn._ceil_to(SHAPES[f], pallas_topn.BLOCK_N)

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(mesh, spec))

    # as `_submit_sharded` serves it: the pack jitted around the scan
    fn = topn._packed(topn._sharded_scan_fn(mesh, 32, False, False, True, None, False, tailed=True))
    lowered = fn.lower(
        shape((f - 2, d * cols), jnp.float32, P(None, "data")),
        shape((1, d * cols), jnp.float32, P(None, "data")),
        (shape((2, d * cols), jnp.float32, P(None, "data")),),
        shape((d,), jnp.int32, P("data")), shape((d,), jnp.int32, P("data")),
        shape((1, batch), jnp.int32, P()),
        shape((1_250_000, f), jnp.float32, P()),
    )
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "oryx_topn_scan" in text and "all-gather" in text
    assert f"f32[{f - 2},{cols}]" in text and f"f32[2,{cols}]" in text
    assert lowered.out_info.shape == (1, batch, 64) and lowered.out_info.dtype == jnp.int32
    mem = compiled.memory_analysis()  # a device's own: its two planes, norms, the staged users
    assert mem.argument_size_in_bytes < (SHAPES[f] * 1.01 + 1_250_000) * f * 4 + 2 * cols * 4


def test_counting_scan_compiles_for_the_v5e(one_chip, no_persistent_cache):
    """The third, SMEM output of ``count_rounds`` (tools/scan_rounds.py and
    tier-1 read it; no served program carries it)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import pallas_topn

    items, features, batch = SHAPES[50], 50, 8
    n_pad = pallas_topn._ceil_to(items, pallas_topn.BLOCK_N)

    shape = functools.partial(_shape, one_chip)
    program = jax.jit(
        functools.partial(
            pallas_topn._streaming_topk_impl,
            k=32, n_items=items, cosine=False, interpret=False, count_rounds=True,
        )
    )
    lowered = program.lower(
        shape((features, n_pad), jnp.float32), shape((1, n_pad), jnp.float32),
        None, None, None, shape((batch, features), jnp.float32),
    )
    assert "tpu_custom_call" in lowered.compile().as_text()
    assert [o.shape for o in lowered.out_info] == [(batch, 32), (batch, 32), (1, 2)]


# The anonymous-visitor cell (PR 40): baskets of 7-8 items ask for howMany + 8
# candidates, the k bucket 32 of the vector DOT program. name: batch rows
ANON_K32_CASES = {f"vector-250f-b{b}-dot-k32": b for b in (8, 32, 128)}


@pytest.mark.parametrize("case", ANON_K32_CASES)
def test_vector_dot_program_at_k_bucket_32_compiles_for_the_v5e(case, one_chip, no_persistent_cache):
    import jax.numpy as jnp

    from oryx_tpu.ops import pallas_topn

    features, batch, k = 250, ANON_K32_CASES[case], 32
    items = SHAPES[features]
    n_pad = pallas_topn._ceil_to(items, pallas_topn.BLOCK_N)
    tail = pallas_topn.tail_rows(features, jnp.float32)
    shape = functools.partial(_shape, one_chip)
    lowered = pallas_topn._streaming_topk_multi.lower(
        shape((features - tail, n_pad), jnp.float32), shape((1, n_pad), jnp.float32),
        None, None, None, shape((1, batch, features), jnp.float32),
        k=k, n_items=items, cosine=False, interpret=False, download_dtype=None,
        tail=shape((tail, n_pad), jnp.float32),
    )
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%oryx_topn_scan" in text
    hits = lowered.out_info  # ONE array a pass: the scores' bits, then the ids
    assert hits.shape == (1, batch, 2 * k) and hits.dtype == jnp.int32


# name: (features, items, item dtype)
GRAM_CASES = {
    "gram-250f-5m": (250, 5_000_000, "float32"),
    "gram-50f-20m": (50, 20_000_000, "float32"),
    "gram-250f-5m-bfloat16": (250, 5_000_000, "bfloat16"),
}


@pytest.mark.parametrize("case", GRAM_CASES)
def test_gram_program_compiles_for_the_v5e(case, one_chip, no_persistent_cache):
    """`oryx_gram` over the planes as they are served (`[250, 5013504]` as a
    main plane of 248 rows and a tail plane of 2): one `[f, f]` float32
    partial a block of 16384 columns, nothing of the matrix copied (the
    program's temporaries are a block's, not the matrix's), float32 products
    at the highest precision."""
    import jax.numpy as jnp

    from oryx_tpu.ops import gram, pallas_topn

    features, items, dtype = GRAM_CASES[case]
    n_pad = pallas_topn._ceil_to(items, pallas_topn.BLOCK_N)
    tail = pallas_topn.tail_rows(features, jnp.dtype(dtype))
    shape = functools.partial(_shape, one_chip)
    n_blocks = n_pad // gram.GRAM_BLOCK
    lowered = gram.oryx_gram.lower(
        shape((features - tail, n_pad), jnp.dtype(dtype)),
        shape((tail, n_pad), jnp.float32) if tail else None,
        shape((), jnp.int32), n_blocks=n_blocks,
    )
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    assert lowered.out_info.shape == (n_blocks, features, features)
    assert lowered.out_info.dtype == jnp.float32
    assert "operand_precision={highest,highest}" in compiled.as_text()
    mem = compiled.memory_analysis()
    planes = features * n_pad * jnp.dtype(dtype).itemsize
    # (a bfloat16 plane of 250 rows is held in 16-row tiles: 256)
    assert planes <= mem.argument_size_in_bytes < planes * 1.03
    # the partials, each tiled to whole (8, 128) vregs at the most
    tiled = pallas_topn._ceil_to(features, 8) * pallas_topn._ceil_to(features, 128)
    assert n_blocks * features * features * 4 <= mem.output_size_in_bytes <= n_blocks * tiled * 4
    assert mem.temp_size_in_bytes < 64 * 2**20  # a block and its product, never a plane


def test_sharded_gram_program_compiles_for_a_v5e_host(no_persistent_cache):
    """`oryx_gram` under `shard_map` over the four-chip configuration's
    matrix (20M x 250 float32, each chip its `[248, cols]` main and `[2,
    cols]` tail plane): every chip its own blocks' partials, masked by its
    own count, and nothing crosses a chip."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from oryx_tpu.ops import gram, pallas_topn

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    d, f = 4, 250
    cols = pallas_topn._ceil_to(SHAPES[f], pallas_topn.BLOCK_N)
    n_blocks = cols // gram.GRAM_BLOCK

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(mesh, spec))

    lowered = gram._sharded_gram_fn(mesh, n_blocks, True).lower(
        shape((f - 2, d * cols), jnp.float32, P(None, "data")),
        (shape((2, d * cols), jnp.float32, P(None, "data")),),
        shape((d,), jnp.int32, P("data")),
    )
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "operand_precision={highest,highest}" in text
    assert not any(c in text for c in ("all-gather", "all-reduce", "all-to-all", "collective-permute"))
    assert lowered.out_info.shape == (d * n_blocks, f, f) and lowered.out_info.dtype == jnp.float32
    mem = compiled.memory_analysis()  # a device's own: its two planes in, its blocks' partials out
    planes = f * cols * 4
    assert planes <= mem.argument_size_in_bytes < planes * 1.03
    tiled = pallas_topn._ceil_to(f, 8) * pallas_topn._ceil_to(f, 128)
    assert n_blocks * f * f * 4 <= mem.output_size_in_bytes <= n_blocks * tiled * 4
    assert mem.temp_size_in_bytes < 64 * 2**20
