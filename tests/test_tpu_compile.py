"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached, so these
tests catch what interpret mode cannot: block shapes and layouts that
Mosaic refuses.  The topology is described inside a fixture (never at
import time) so that only the worker running this file loads libtpu.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.confidence import confidence_fused

LLADA_VOCAB = get_config("llada-8b").vocab_size          # 126464
WHISPER_VOCAB = get_config("whisper-medium").vocab_size  # 51865: ragged tile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dtype,vocab", [
    (jnp.bfloat16, LLADA_VOCAB),
    (jnp.float32, LLADA_VOCAB),
    (jnp.bfloat16, WHISPER_VOCAB),
], ids=["bf16-126464", "f32-126464", "bf16-51865"])
def test_confidence_kernel_compiles_for_v5e(one_chip, dtype, vocab):
    logits = jax.ShapeDtypeStruct((2, 256, vocab), dtype, sharding=one_chip)
    fn = jax.jit(lambda x: confidence_fused(x, interpret=False))
    text = fn.lower(logits).compile().as_text()
    assert "tpu_custom_call" in text
