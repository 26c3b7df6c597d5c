"""Compile rehearsals at real widths for the TPU v5e (no chip needed):
the float32 indexed-submit scan programs that the two configurations
dispatch, at the batch buckets their traffic can meet (8, 16, 32 in a
calm window; 64 and 128 behind a pause of the machine, since the open
mix keeps up to 128 requests outstanding) and the k bucket of howMany=10
with ten known items (32). What the chip's compiler would refuse (VMEM,
tiling, device memory) is refused here, at no chip time. A compile that
passes is not a chip run and says nothing about time.

The topology is described inside a module fixture, never at import
(on-chip-measurement guide, section 2): one worker loads libtpu, and only
when a test of this file starts."""

import json

import pytest

from benchmark.spec import ROOT

CONFIGS = ["als-50f-20m-f32", "als-250f-5m-f32"]
BUCKETS = [8, 16, 32, 64, 128]
K_BUCKET = 32


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


@pytest.mark.parametrize("batch", BUCKETS)
@pytest.mark.parametrize("config", CONFIGS)
def test_indexed_scan_program_compiles_for_the_v5e(config, batch, one_chip, no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from oryx_tpu.ops import pallas_topn

    cfg = json.loads((ROOT / "benchmark" / "configs" / f"{config}.json").read_text())
    items, features, users = cfg["items"], cfg["features"], cfg["users"]
    n_pad = max(pallas_topn.BLOCK_N, pallas_topn._ceil_to(items, pallas_topn.BLOCK_N))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    mat_t = shape((features, n_pad), jnp.float32)  # the kernel's feature-major layout
    norms = shape((1, n_pad), jnp.float32)
    x_dev = shape((int(users * 1.25), features), jnp.float32)  # staged with 25 % headroom
    idx_kb = shape((1, batch), jnp.int32)
    lowered = pallas_topn._streaming_topk_multi_indexed.lower(
        mat_t, norms, None, None, None, x_dev, idx_kb,
        k=K_BUCKET, n_items=items, cosine=False, interpret=False, download_dtype=None,
    )
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the program's own arguments: the item matrix as the device holds it
    # (feature rows padded to the 8-sublane tile) and the staged users
    assert mem.argument_size_in_bytes >= items * features * 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
