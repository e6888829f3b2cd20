"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached, so these
tests catch what interpret mode cannot: block shapes and layouts that
Mosaic refuses.  The topology is described inside a fixture (never at
import time) so that only the worker running this file loads libtpu.
"""
import re

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


def test_confidence_kernel_reads_the_cell_logits_in_place(one_chip):
    """At the benchmark cell's shape the kernel's custom call takes the
    2-D f32[1024,126464] logits the (4, 256) positions flatten to, with
    no pad before it: the operand ``bench.trace_reduce.kernel_calls``
    reads rows and vocabulary from."""
    logits = jax.ShapeDtypeStruct((4, 256, LLADA_VOCAB), jnp.float32,
                                  sharding=one_chip)
    fn = jax.jit(lambda x: confidence_fused(x, interpret=False))
    lowered = fn.lower(logits)
    module = lowered.as_text()
    assert "stablehlo.pad" not in module
    assert re.search(r"tpu_custom_call.*\(tensor<1024x126464xf32>\)",
                     module)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"\bpad\(", text)
    lines = text.splitlines()
    call = next(ln for ln in lines
                if "tpu_custom_call" in ln and "custom-call(" in ln)
    operand = re.search(r"custom-call\((%[\w.-]+)\)", call)[1]
    defn = next(ln for ln in lines if ln.strip().startswith(operand + " "))
    # a bitcast of the caller's logits (no copy): same bytes, 2-D view
    assert re.search(r"= f32\[1024,126464\]\{[^}]*\} "
                     r"(bitcast|parameter)\(", defn), defn


@pytest.mark.parametrize("rows,k,n", [(512, 2048, 768), (512, 768, 2048),
                                      (32768, 2048, 768), (520, 2048, 768)],
                         ids=["window-gate", "window-down", "refresh-gate",
                              "ragged-rows"])
def test_expert_kernel_compiles_for_v5e(one_chip, rows, k, n):
    """The dropless expert layer's grouped matmul lowered for the chip
    (``models.moe.grouped_matmul``, which picks its kernel by the
    platform it is lowered for): the megablox kernel over SDAR-30B-A3B's
    16 held experts of 2048 x 768, the whole matrix a step, at the cell's
    window and refresh buffers, and a buffer padded to whole tiles."""
    from repro.models.moe import grouped_matmul
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((16, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    lowered = jax.jit(grouped_matmul).lower(x, w, sizes)
    assert lowered.out_info.shape == (rows, n)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert "ragged" not in text
