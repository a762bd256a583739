"""core.redistribute dtype-in-flight: narrowing casts happen BEFORE the
collective and widening casts AFTER, so the wire carries the narrow form
(paper §4.2 reduced-precision transfer).

The wire dtype is pinned on :func:`relayout_explicit` — the shard_map path
whose documented purpose is to "validate that the GSPMD path moves the
bytes we claim" (the GSPMD path's collective placement is the partitioner's
choice and old XLA versions reorder the convert).  The production
:func:`relayout` is pinned on numerics + result dtype."""

import os
import re

import pytest

DEVS = 8


def _in_child() -> bool:
    return os.environ.get("REPRO_REDIST_CHILD") == str(DEVS)


if not _in_child():
    def test_redistribute_dtype_subprocess():
        import _childsuite
        rc, out = _childsuite.join("test_redistribute_dtype.py", timeout=600)
        if rc != 0:
            pytest.fail("child failed:\n" + out)
else:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.layout import Layout
    from repro.core.redistribute import relayout, relayout_explicit
    from repro.launch.mesh import make_mesh

    SRC = Layout.row_sharded(2, axis="model")
    DST = Layout.replicated(2)

    @pytest.fixture(scope="module")
    def mesh():
        return make_mesh((2, 4), ("data", "model"))

    def _explicit_hlo(mesh, x_dtype, out_dtype):
        """Lowered (pre-optimization) program text + result.

        The wire dtype is asserted on the program *we* emit — backend
        simplifiers on some XLA versions reorder convert/all-gather, which
        is exactly why the claim needs pinning at this level."""
        x = jax.random.normal(jax.random.PRNGKey(0), (32, 16)).astype(x_dtype)
        x = jax.device_put(x, SRC.sharding(mesh))

        def f(a):
            return relayout_explicit(a, SRC, DST, mesh, dtype=out_dtype)

        jitted = jax.jit(f, in_shardings=SRC.sharding(mesh))
        return jitted.lower(x).as_text(), jitted(x)

    def _allgather_dtypes(txt):
        """Element dtypes moved by every all_gather in the lowered text."""
        return set(re.findall(
            r"stablehlo\.all_gather.*?\(tensor<[0-9x]+x([a-z0-9]+)>\)",
            txt, re.DOTALL))

    def test_narrowing_casts_before_collective(mesh):
        """fp32 -> bf16 relayout: the all-gather moves bf16, never f32."""
        hlo, out = _explicit_hlo(mesh, jnp.float32, jnp.bfloat16)
        dts = _allgather_dtypes(hlo)
        assert "bf16" in dts and "f32" not in dts, dts
        assert out.dtype == jnp.bfloat16

    def test_widening_casts_after_collective(mesh):
        """bf16 -> fp32 relayout: the wire still sees bf16; the widen
        happens after the gather."""
        hlo, out = _explicit_hlo(mesh, jnp.bfloat16, jnp.float32)
        dts = _allgather_dtypes(hlo)
        assert "bf16" in dts and "f32" not in dts, dts
        assert out.dtype == jnp.float32

    def test_explicit_narrowing_values_match_pre_cast(mesh):
        """Numerics: narrowing in flight == casting first, then moving."""
        x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
        xs = jax.device_put(x, SRC.sharding(mesh))
        got = jax.jit(lambda a: relayout_explicit(
            a, SRC, DST, mesh, dtype=jnp.bfloat16),
            in_shardings=SRC.sharding(mesh))(xs)
        want = np.asarray(x.astype(jnp.bfloat16), np.float32)
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)

    @pytest.mark.parametrize("x_dtype,out_dtype", [
        (jnp.float32, jnp.bfloat16),      # narrowing
        (jnp.bfloat16, jnp.float32),      # widening (lossless)
    ])
    def test_gspmd_relayout_values_and_dtype(mesh, x_dtype, out_dtype):
        """The production GSPMD path keeps the same value/dtype contract."""
        x = jax.random.normal(jax.random.PRNGKey(2), (32, 16)).astype(x_dtype)
        xs = jax.device_put(x, SRC.sharding(mesh))
        got = jax.jit(lambda a: relayout(a, DST, mesh, dtype=out_dtype),
                      in_shardings=SRC.sharding(mesh))(xs)
        assert got.dtype == out_dtype
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(x.astype(out_dtype), np.float32))
