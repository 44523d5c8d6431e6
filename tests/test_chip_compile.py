"""Compile rehearsals for one TPU v5e chip, made without the chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached.  These tests compile the serving decode kernel,
its walk bounded per row, at the published head layouts of the main-path
models, so what the chip's compiler would refuse (block shapes, fast-memory
use) fails here at no chip time.  A compile that passes is not a chip run:
nothing executes.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
import functools

import pytest

# (q heads, kv heads, key width, value width, query dtype, sliding window)
# per published config; MLA attends its compressed latents as one shared
# kv head (key = kv_lora_rank + qk_rope_dim, value = kv_lora_rank, f32
# queries)
CASES = {
    "qwen3-4b-gqa": (32, 8, 128, 128, "bfloat16", 0),
    "gemma-2b-mqa": (8, 1, 256, 256, "bfloat16", 0),
    "deepseek-v3-mla": (128, 1, 576, 512, "float32", 0),
    "mixtral-8x7b-swa": (32, 8, 128, 128, "bfloat16", 4096),
}
BATCH, BLOCK, TABLE_BLOCKS = 8, 16, 128       # 8 slots x 2048 tokens


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("residuals", [False, True])
@pytest.mark.parametrize("case", list(CASES))
def test_paged_decode_compiles_for_v5e(one_chip, no_persistent_cache, case,
                                       residuals):
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_decode import paged_flash_decode
    nq, nkv, dk, dv, qdt, window = CASES[case]
    phys = (2 + BATCH * TABLE_BLOCKS) * BLOCK

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((BATCH, nq, dk), jnp.dtype(qdt)),
            sds((phys, nkv, dk), jnp.bfloat16),
            sds((phys, nkv, dv), jnp.bfloat16),
            sds((phys,), jnp.int32),
            sds((BATCH, TABLE_BLOCKS), jnp.int32),
            sds((BATCH,), jnp.int32))
    live = sds((BATCH,), jnp.int32)       # the walk's bound, one per row
    fn = functools.partial(paged_flash_decode, block=BLOCK, window=window,
                           impl="pallas", interpret=False,
                           return_residuals=residuals)
    compiled = jax.jit(
        lambda *a: fn(*a[:-1], live=a[-1])).lower(*args, live).compile()
    assert "tpu_custom_call" in compiled.as_text()
