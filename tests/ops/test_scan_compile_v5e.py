"""Compile rehearsals for the TPU v5e (no chip needed) of the scan programs
that no benchmark cell compiles: tests/benchmark/test_bench_compile_v5e.py
holds the float32 programs of the cells (batch buckets 8-128, k bucket 32);
here are the other item dtypes (bfloat16; int8 two-plane, whose kernel keeps
128 candidates for the rescore), the k buckets 16, 128 and 256, and the
widest scan group (256 rows), at 50 and at 250 features. What the chip's
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
    (vals, idxs) = lowered.out_info
    assert vals.shape == idxs.shape == (1, batch, k)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


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
