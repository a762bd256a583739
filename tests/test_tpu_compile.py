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

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.kernels import fused, ops, paged_attention
from repro.launch.mesh import make_host_mesh
from repro.models import Model

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
    pool = (2, n_pages, PAGE, HKV * HD)
    f = jax.jit(paged_attention.paged_decode_attention)
    compiled = f.lower(
        _sds((batch, HQ, HD), jnp.bfloat16, one_chip),
        _sds(pool, jnp.bfloat16, one_chip),
        _sds(pool, jnp.bfloat16, one_chip),
        _sds((batch, n_row), jnp.int32, one_chip),
        _sds((batch,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


# ---------------------------------------------------------------------------
# The paged model steps touch the pool only where it changes
# ---------------------------------------------------------------------------

#: pages of the guard's pool: a few hundred, a count no other dim has
N_POOL = 301
N_SLOTS, N_ROW, CHUNK = 8, 32, 32

#: ``%name = bf16[d0,d1,...]{layout} opcode(`` of one HLO instruction
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _paged_model(one_chip, monkeypatch):
    """A 2-layer dense model at qwen2-0.5b's head widths on the described
    chip, with the Pallas paged kernel selected as on a TPU backend."""
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setattr(ops, "_PROBED", True)
    monkeypatch.setattr(ops, "_PROBE_ERROR", None)
    cfg = ModelConfig(name="paged-guard", family="dense", n_layers=2,
                      d_model=896, n_heads=HQ, n_kv_heads=HKV, head_dim=HD,
                      d_ff=1024, vocab_size=1024, qkv_bias=True)
    mesh = make_host_mesh(devices=list(one_chip.device_set))
    model = Model(cfg, mesh)

    def shapes(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    with jax.set_mesh(mesh):
        params = shapes(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0))))
        pool = shapes(jax.eval_shape(
            lambda: model.init_paged_pool(N_POOL, PAGE)))
    return model, mesh, params, pool


def _layer_pool_copies(hlo: str):
    """Instructions whose result is one whole layer of the pool, in the
    merged ``(P, page, Hkv*hd)`` or the split ``(P, page, Hkv, hd)``
    layout (with or without a unit layer dim), other than parameters and
    bitcasts, and copies of the whole stacked pool."""
    layer = {(N_POOL, PAGE, HKV * HD), (N_POOL, PAGE, HKV, HD)}
    layer |= {(1,) + s for s in layer}
    stacked = {(2,) + s for s in layer if s[0] == N_POOL}
    bad = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or not m.group(1):
            continue
        dims, op = tuple(int(d) for d in m.group(1).split(",")), m.group(2)
        if dims in layer and op not in ("parameter", "bitcast"):
            bad.append(line.strip()[:160])
        if dims in stacked and op in ("copy", "copy-start", "transpose"):
            bad.append(line.strip()[:160])
    return bad


def test_decode_step_paged_reads_the_pool_in_place(one_chip, monkeypatch):
    """No layer of the pool is sliced out, relaid for the kernel or
    written back: each layer's token scatters into the stacked pool and
    the kernel reads that pool where it lies."""
    model, mesh, params, pool = _paged_model(one_chip, monkeypatch)
    cache = dict(pool, table=_sds((N_SLOTS, N_ROW), jnp.int32, one_chip))
    with jax.set_mesh(mesh):
        compiled = jax.jit(model.decode_step_paged, donate_argnums=(1,)).lower(
            params, cache, _sds((N_SLOTS, 1), jnp.int32, one_chip),
            _sds((N_SLOTS,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
    assert _layer_pool_copies(compiled.as_text()) == []


def test_prefill_chunk_paged_reads_the_pool_in_place(one_chip, monkeypatch):
    """The chunk scatters into the stacked pool and only the sequence's
    row is gathered back: no whole layer is sliced out or written back."""
    model, mesh, params, pool = _paged_model(one_chip, monkeypatch)
    with jax.set_mesh(mesh):
        compiled = jax.jit(model.prefill_chunk_paged,
                           donate_argnums=(1,)).lower(
            params, pool, _sds((1, CHUNK), jnp.int32, one_chip),
            _sds((N_ROW,), jnp.int32, one_chip),
            _sds((), jnp.int32, one_chip)).compile()
    assert _layer_pool_copies(compiled.as_text()) == []


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


# ---------------------------------------------------------------------------
# Latent attention (DeepSeek-V2): the kernel at published widths, and the
# model steps touching the latent pool in place
# ---------------------------------------------------------------------------

#: DeepSeek-V2-Lite: 16 heads over a 512 + 64 latent; 256-position pages
MLA_H, MLA_R, MLA_DR, MLA_PAGE = 16, 512, 64, 256


def test_paged_decode_latent_compiles_for_v5e(one_chip):
    batch, n_row = 32, 32
    pages = 1 + batch * n_row
    f = jax.jit(functools.partial(
        paged_attention.paged_decode_latent_attention, scale=0.1147))
    compiled = f.lower(
        _sds((batch, MLA_H, MLA_R + MLA_DR), jnp.float32, one_chip),
        _sds((27, pages, MLA_PAGE, MLA_R), jnp.bfloat16, one_chip),
        _sds((27, pages, MLA_PAGE // 2, 2 * MLA_DR), jnp.bfloat16,
             one_chip),
        _sds((batch, n_row), jnp.int32, one_chip),
        _sds((batch,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
    # the pool is read where it lies: no temporary near its size
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _mla_model(one_chip, monkeypatch):
    """DeepSeek-V2-Lite's attention and expert widths at two layers (the
    dense one and one MoE layer of 8 held experts), small vocabulary."""
    import dataclasses

    from repro.configs import get_config
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setattr(ops, "_PROBED", True)
    monkeypatch.setattr(ops, "_PROBE_ERROR", None)
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), n_layers=2,
                              vocab_size=1024, expert_shard=(0, 8))
    mesh = make_host_mesh(devices=list(one_chip.device_set))
    model = Model(cfg, mesh)

    def shapes(tree):
        return jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    with jax.set_mesh(mesh):
        params = shapes(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0))))
        pool = shapes(jax.eval_shape(
            lambda: model.init_paged_pool(N_POOL, MLA_PAGE)))
    return model, mesh, params, pool


def _latent_pool_copies(hlo: str):
    """Instructions whose result is one whole layer of either latent pool
    array, other than parameters and bitcasts, and copies or transposes
    of a whole stacked array."""
    layer = {(N_POOL, MLA_PAGE, MLA_R), (N_POOL, MLA_PAGE // 2, 2 * MLA_DR)}
    layer |= {(1,) + s for s in layer}
    stacked = {(2,) + s for s in layer if s[0] == N_POOL}
    bad = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or not m.group(1):
            continue
        dims, op = tuple(int(d) for d in m.group(1).split(",")), m.group(2)
        if dims in layer and op not in ("parameter", "bitcast"):
            bad.append(line.strip()[:160])
        if dims in stacked and op in ("copy", "copy-start", "transpose"):
            bad.append(line.strip()[:160])
    return bad


def test_mla_decode_step_reads_the_latent_pool_in_place(one_chip,
                                                        monkeypatch):
    """The absorbed decode step scatters each layer's latent rows into the
    stacked pool and the latent kernel reads it there: no relayout or
    copy of the pool, and no temporary near a layer's size."""
    model, mesh, params, pool = _mla_model(one_chip, monkeypatch)
    n_row = 8
    cache = dict(pool, table=_sds((N_SLOTS, n_row), jnp.int32, one_chip))
    with jax.set_mesh(mesh):
        compiled = jax.jit(model.decode_step_paged, donate_argnums=(1,)
                           ).lower(params, cache,
                                   _sds((N_SLOTS, 1), jnp.int32, one_chip),
                                   _sds((N_SLOTS,), jnp.int32, one_chip)
                                   ).compile()
    _assert_kernel(compiled)
    assert _latent_pool_copies(compiled.as_text()) == []
    layer_bytes = N_POOL * MLA_PAGE * (MLA_R + MLA_DR) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes // 4


def test_mla_prefill_chunk_reads_the_latent_pool_in_place(one_chip,
                                                          monkeypatch):
    """A prefill chunk scatters its latent rows into the stacked pool and
    gathers back only the sequence's pages to decompress."""
    model, mesh, params, pool = _mla_model(one_chip, monkeypatch)
    n_row = 8
    with jax.set_mesh(mesh):
        compiled = jax.jit(model.prefill_chunk_paged,
                           donate_argnums=(1,)).lower(
            params, pool, _sds((1, CHUNK), jnp.int32, one_chip),
            _sds((n_row,), jnp.int32, one_chip),
            _sds((), jnp.int32, one_chip)).compile()
    assert _latent_pool_copies(compiled.as_text()) == []
