"""Compiles for a described TPU v5e chip: the three Pallas kernels at the
widths of the configs that use them, and ResNet50's first fused block.

Nothing runs here; the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, too much VMEM).  Interpret-mode
parity tests cannot see either.  The topology is described inside a
module-scoped fixture, so only the worker that runs these tests loads the
TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# granite-moe-3b-a800m attention: 24 query heads, 8 KV heads, head_dim 64
def test_flash_attention_compiles_for_v5e(spec):
    q = spec((1, 2048, 24, 64))
    kv = spec((1, 2048, 8, 64))
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_for_v5e(spec):
    q = spec((8, 24, 64))
    kv = spec((8, 4096, 8, 64))
    lengths = spec((8,), jnp.int32)
    text = _compiled_text(
        lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False),
        q, kv, kv, lengths)
    assert "tpu_custom_call" in text


# zamba2-2.7b SSD: 80 heads of ssm_head_dim 64, state 64, chunk 128
@pytest.mark.parametrize("chunk", [128, 64])
def test_ssd_scan_compiles_for_v5e(spec, chunk):
    x = spec((1, 2048, 80, 64))
    log_a = spec((1, 2048, 80), jnp.float32)
    text = _compiled_text(
        lambda x, a, b, c: ssd_scan(x, a, b, c, chunk=chunk,
                                    interpret=False),
        x, log_a, x, x)
    assert "tpu_custom_call" in text


def test_resnet50_first_block_compiles_for_v5e(spec):
    from repro.core.graph import fuse_blocks
    from repro.models import cnn_zoo

    block = fuse_blocks(cnn_zoo.build("ResNet50"))[0]
    (in_spec,) = block.in_specs
    x = spec((8, *in_spec.shape[1:]), in_spec.dtype)
    compiled = jax.jit(block.make_callable()).lower(x).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
