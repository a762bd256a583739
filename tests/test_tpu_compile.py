"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, too much VMEM).  Interpret mode
checks none of that.  Every test asserts that the compiled program holds
the Mosaic kernel (``tpu_custom_call``), so a silent fallback to the
reference cannot pass.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.  Keep these tests in this one file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused, paged_attention

#: qwen2-0.5b decode widths: 14 query heads over 2 KV heads (g=7), hd 64
HQ, HKV, HD, PAGE = 14, 2, 64, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep them out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch,n_row", [(8, 32), (1, 1)])
def test_paged_decode_compiles_for_v5e(one_chip, batch, n_row):
    n_pages = 1 + batch * n_row
    f = jax.jit(paged_attention.paged_decode_attention)
    compiled = f.lower(
        _sds((batch, HQ, HD), jnp.bfloat16, one_chip),
        _sds((n_pages, PAGE, HKV, HD), jnp.bfloat16, one_chip),
        _sds((n_pages, PAGE, HKV, HD), jnp.bfloat16, one_chip),
        _sds((batch, n_row), jnp.int32, one_chip),
        _sds((batch,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


#: a 32 MiB fp32 gradient bucket
BUCKET = (32 << 20) // 4


def test_quantize_compress_compiles_for_v5e(one_chip):
    compiled = jax.jit(fused.quantize_compress).lower(
        _sds((BUCKET,), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)


def test_quantize_int8_compiles_for_v5e(one_chip):
    compiled = jax.jit(fused.quantize_int8).lower(
        _sds((BUCKET,), jnp.float32, one_chip),
        _sds((), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)
