"""No silent device defaults: unknown chips raise, a failed Pallas probe
on TPU raises, and the compile cache goes where the caller says."""

from __future__ import annotations

import os
import types

import jax
import numpy as np
import pytest

from repro.core import chips
from repro.core import memory as mem
from repro.kernels import ops, roofline
from repro.launch import compile_cache
from repro.pipeline import costs


def _fake_device(kind):
    return types.SimpleNamespace(device_kind=kind, platform="tpu")


def _fake_mesh(kind):
    return types.SimpleNamespace(devices=np.array([_fake_device(kind)]))


@pytest.mark.parametrize("kind,key", [("TPU v5 lite", "v5e"),
                                      ("TPU v5e", "v5e"), ("cpu", "cpu")])
def test_known_kinds_resolve(kind, key):
    assert chips.chip_key(kind) == key
    assert mem.budget_for(_fake_mesh(kind)).platform == key


def test_v5e_peaks_are_the_published_ones():
    v5e = chips.CHIPS["v5e"]
    assert (v5e.peak_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert v5e.hbm_bytes == 16 * chips.GIB


def test_unknown_tpu_kind_raises_everywhere(monkeypatch):
    with pytest.raises(ValueError, match="TPU v99"):
        chips.chip_key("TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        mem.budget_for(_fake_mesh("TPU v99"))
    with pytest.raises(ValueError):
        mem.budget_for(platform="v99")
    monkeypatch.setattr(jax, "devices", lambda: [_fake_device("TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        costs.device_flops()
    with pytest.raises(ValueError, match="TPU v99"):
        roofline.ridge_intensity()


def test_peak_and_ridge_follow_the_device(monkeypatch):
    assert costs.device_flops() == chips.CHIPS["cpu"].peak_flops
    monkeypatch.setattr(jax, "devices", lambda: [_fake_device("TPU v5 lite")])
    assert costs.device_flops() == 197e12
    assert roofline.ridge_intensity() == pytest.approx(197e12 / 819e9)


def _on_tpu(monkeypatch, probe_error):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_PROBED", True)
    monkeypatch.setattr(ops, "_PROBE_ERROR", probe_error)
    monkeypatch.delenv("REPRO_KERNELS", raising=False)


def test_failed_probe_raises_on_tpu(monkeypatch):
    _on_tpu(monkeypatch, RuntimeError("Mosaic lowering failed"))
    assert ops.backend() == "pallas"
    with pytest.raises(RuntimeError, match="TPU") as info:
        ops.resolve("paged_decode_attention")
    assert "Mosaic lowering failed" in str(info.value.__cause__)


def test_failed_probe_demotes_on_cpu(monkeypatch):
    monkeypatch.setattr(ops, "_PROBED", True)
    monkeypatch.setattr(ops, "_PROBE_ERROR", RuntimeError("no Mosaic"))
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    assert ops.resolve("paged_decode_attention") == "ref"


def test_interpret_mode_refused_on_tpu(monkeypatch):
    _on_tpu(monkeypatch, None)
    assert ops.resolve("x") == "pallas"
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    with pytest.raises(ValueError, match="interpret"):
        ops.resolve("x")


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_caller_dir_wins(monkeypatch, tmp_path, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # nothing else set


def test_compile_cache_defaults_into_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = compile_cache.enable()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert d == os.path.join(root, ".cache", "jax")
    assert jax.config.jax_compilation_cache_dir == d
