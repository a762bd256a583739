"""Fused-kernel conformance sweep: every Pallas kernel in
``repro.kernels.fused`` / ``paged_attention`` / ``gemm.matmul_dequant``
pinned to its pure-jnp oracle, plus the dispatch layer's graceful
fallback and the fused comms wire format against ``comms/compressed.py``.

Runs in a child process with 4 fake host devices (collection-time overlap
via ``_childsuite``) so the fused ``sync_tree`` pack can exercise a real
group ``pmax``; the Pallas kernels themselves run in interpret mode (the
Mosaic emulator — the only Pallas this CPU container has).
"""

import os

import pytest

DEVS = 4


def _in_child() -> bool:
    return os.environ.get("REPRO_FUSED_CHILD") == str(DEVS)


if not _in_child():
    def test_fused_kernels_subprocess():
        import _childsuite
        rc, out = _childsuite.join("test_fused_kernels.py")
        if rc != 0:
            pytest.fail("child failed:\n" + out)
else:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.comms import CommsPlan, compressed, sync_tree
    from repro.comms import bucketer
    from repro.kernels import fused, gemm, ops, paged_attention, ref
    from repro.kernels import roofline

    # tolerance pinned per activation dtype (fp32 accumulation everywhere;
    # bf16 operands round at 8 mantissa bits)
    TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
           jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}

    def _rand(shape, seed=0, dtype=jnp.float32):
        x = jax.random.normal(jax.random.PRNGKey(seed), shape,
                              dtype=jnp.float32)
        return x.astype(dtype)

    # ------------------------------------------------------------------
    # fused quantize-compress
    # ------------------------------------------------------------------
    @pytest.mark.parametrize("n", [4096, 32 * 128, 5000, 123, 1])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_quantize_compress_matches_reference(n, dtype):
        # non-power-of-two tails: the kernel zero-pads to (32,128) tiles;
        # zero padding cannot raise the absmax, so q AND scale are exact
        x = _rand((n,), seed=n, dtype=dtype)
        q, s = fused.quantize_compress(x, interpret=True)
        # jit the oracle: production always runs it inside jit, where XLA
        # folds `absmax/127 + eps` identically to the kernel interpreter;
        # EAGER dispatch rounds the divide 1 ulp differently, which flips
        # values sitting exactly on a .5 rounding boundary (common for
        # coarse bf16 inputs) — a comparison artifact, not a numerics gap.
        qr, sr = jax.jit(ref.quantize_compress)(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        assert float(s) == float(sr)

    def test_quantize_compress_multidim_shape_preserved():
        x = _rand((7, 33, 5), seed=3)
        q, _ = fused.quantize_compress(x, interpret=True)
        assert q.shape == x.shape and q.dtype == jnp.int8

    @pytest.mark.parametrize("n", [4096, 777])
    def test_quantize_int8_matches_reference(n):
        x = _rand((n,), seed=n)
        scale = jnp.float32(0.0173)
        q = fused.quantize_int8(x, scale, interpret=True)
        np.testing.assert_array_equal(np.asarray(q),
                                      np.asarray(ref.quantize_int8(x, scale)))

    def test_quantize_compress_is_compressed_py_wire_format():
        """The fused kernel must emit EXACTLY the affine format
        comms/compressed.py puts on the wire (scale=absmax/127+1e-12,
        q=clip(round(x/scale))) — dequant round-trips within scale/2."""
        x = _rand((5000,), seed=9)
        q, s = fused.quantize_compress(x, interpret=True)

        @jax.jit
        def wire(x):                            # the compressed.py formula
            v = x.astype(jnp.float32)
            scale = jnp.max(jnp.abs(v)) / 127.0 + 1e-12
            return jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)

        q_wire = wire(x)
        v = np.asarray(x, np.float32)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_wire))
        # dequantization error of the round-trip is bounded by scale/2
        err = np.abs(np.asarray(q, np.float32) * float(s) - v)
        assert err.max() <= float(s) * 0.5 + 1e-6

    # ------------------------------------------------------------------
    # dequant-fused GEMM epilogue
    # ------------------------------------------------------------------
    @pytest.mark.parametrize("mkn", [(8, 256, 128), (32, 128, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matmul_dequant_kernel_matches_reference(mkn, dtype):
        m, k, n = mkn
        a = _rand((m, k), seed=1, dtype=dtype)
        bq, bs = ref.quantize_int8_per_channel(_rand((k, n), seed=2))
        got = gemm.matmul_dequant(a, bq, bs, bm=min(8, m), bn=128, bk=128,
                                  out_dtype=jnp.float32, interpret=True)
        want = ref.matmul_dequant(a, bq, bs, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL[dtype])

    @pytest.mark.parametrize("mkn", [(5, 300, 77), (130, 257, 129)])
    def test_matmul_dequant_dispatch_pads_ragged_shapes(monkeypatch, mkn):
        # ops.matmul_dequant zero-pads to tile multiples and slices back
        m, k, n = mkn
        monkeypatch.setenv("REPRO_KERNELS", "interpret")
        a = _rand((m, k), seed=4)
        bq, bs = ref.quantize_int8_per_channel(_rand((k, n), seed=5))
        got = ops.matmul_dequant(a, bq, bs)
        want = ref.matmul_dequant(a, bq, bs)
        assert got.shape == (m, n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    # ------------------------------------------------------------------
    # paged-attention decode
    # ------------------------------------------------------------------
    #: layers of the stacked test pool
    N_LAYERS = 3

    def _paged_case(seed, B, Hq, Hkv, hd, page, nb, dtype, permute=True):
        """A stacked ``(L, P, page, Hkv*hd)`` pool, each layer its own
        random draw, with a (permuted) block table and ragged lengths."""
        rng = np.random.default_rng(seed)
        P = B * nb
        q = _rand((B, Hq, hd), seed=seed, dtype=dtype)
        kp = _rand((N_LAYERS, P, page, Hkv * hd), seed=seed + 1, dtype=dtype)
        vp = _rand((N_LAYERS, P, page, Hkv * hd), seed=seed + 2, dtype=dtype)
        phys = rng.permutation(P) if permute else np.arange(P)
        tbl = jnp.asarray(phys.reshape(B, nb).astype(np.int32))
        lens = jnp.asarray(
            rng.integers(1, nb * page + 1, size=B).astype(np.int32))
        return q, kp, vp, tbl, lens

    @pytest.mark.parametrize("layer", [0, N_LAYERS - 1])
    @pytest.mark.parametrize("gqa", [(8, 4), (4, 4), (6, 2)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_paged_decode_matches_reference(gqa, dtype, layer):
        # permuted block tables prove the kernel really reads through the
        # indices table; ragged seq_lens exercise the per-page mask tails;
        # a layer other than 0 proves it reads the layer it is given
        Hq, Hkv = gqa
        q, kp, vp, tbl, lens = _paged_case(11, 3, Hq, Hkv, 64, 16, 4,
                                           dtype)
        lyr = jnp.asarray(layer, jnp.int32)
        got = paged_attention.paged_decode_attention(q, kp, vp, tbl, lens,
                                                     lyr, interpret=True)
        want = ref.paged_decode_attention(q, kp, vp, tbl, lens, lyr)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])
        other = ref.paged_decode_attention(q, kp, vp, tbl, lens, 1)
        assert not np.allclose(np.asarray(got, np.float32),
                               np.asarray(other, np.float32), **TOL[dtype])

    @pytest.mark.parametrize("layer", [0, N_LAYERS - 1])
    def test_paged_oracle_matches_dense_decode_attention(layer):
        """The paged oracle with an identity table equals the production
        dense-cache decode attention (models/layers.decode_attention) of
        the same layer — the semantics the serving engine swaps out."""
        from repro.models import layers
        B, Hq, Hkv, hd, page, nb = 2, 8, 4, 32, 8, 3
        q, kp, vp, tbl, lens = _paged_case(7, B, Hq, Hkv, hd, page, nb,
                                           jnp.float32, permute=False)
        pos = int(lens.max()) - 1
        lens = jnp.full((B,), pos + 1, jnp.int32)      # lockstep decode
        T = nb * page
        k_dense = np.asarray(kp)[layer].reshape(B, T, Hkv, hd)
        v_dense = np.asarray(vp)[layer].reshape(B, T, Hkv, hd)
        want = layers.decode_attention(
            q[:, :, None, :], jnp.asarray(k_dense), jnp.asarray(v_dense),
            jnp.asarray(pos, jnp.int32))[:, :, 0, :]
        got = ref.paged_decode_attention(q, kp, vp, tbl, lens,
                                         jnp.asarray(layer, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    # ------------------------------------------------------------------
    # dispatch: graceful fallback + roofline gate
    # ------------------------------------------------------------------
    def test_pallas_unavailable_falls_back_to_ref(monkeypatch):
        """REPRO_KERNELS=pallas on a backend without Mosaic must never
        crash: the availability probe demotes every fused op to its
        reference — the asterisked-fallback discipline of dMath §4.1."""
        monkeypatch.setenv("REPRO_KERNELS", "pallas")
        assert ops.backend() == "pallas"
        assert not ops.pallas_supported()      # CPU container: no Mosaic
        assert ops.resolve("probe") == "ref"
        x = _rand((5000,), seed=21)
        q, s = ops.quantize_compress(x)        # would crash without demote
        qr, sr = ref.quantize_compress(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        a = _rand((4, 64), seed=22)
        bq, bs = ref.quantize_int8_per_channel(_rand((64, 32), seed=23))
        np.testing.assert_allclose(
            np.asarray(ops.matmul_dequant(a, bq, bs)),
            np.asarray(ref.matmul_dequant(a, bq, bs)), rtol=1e-6)

    def test_default_backend_on_cpu_is_ref(monkeypatch):
        monkeypatch.delenv("REPRO_KERNELS", raising=False)
        assert ops.backend() == "ref"

    def test_roofline_gate_memory_vs_compute_bound():
        d = roofline.gate("x", flops=1e3, bytes_ref=1e6, bytes_fused=5e5)
        assert d.fused and "memory bound" in d.reason
        d = roofline.gate("x", flops=1e12, bytes_ref=1e6, bytes_fused=5e5)
        assert not d.fused and "compute bound" in d.reason
        d = roofline.gate("x", flops=1e3, bytes_ref=1e6, bytes_fused=1e6)
        assert not d.fused and "saves no bytes" in d.reason

    def test_dispatch_report_records_decisions(monkeypatch):
        monkeypatch.setenv("REPRO_KERNELS", "interpret")
        ops.quantize_compress(_rand((4096,), seed=31))
        rep = ops.dispatch_report()
        assert rep["backend"] == "interpret"
        assert "quantize_compress" in rep["ops"]
        assert rep["ops"]["quantize_compress"]["active"] is True

    # ------------------------------------------------------------------
    # fused comms pack: bitwise-identical wire numerics
    # ------------------------------------------------------------------
    @pytest.fixture(scope="module")
    def mesh():
        assert len(jax.devices()) == DEVS
        return jax.make_mesh((DEVS,), ("data",))

    def _tree(seed=0):
        rng = np.random.default_rng(seed)
        return {"w": jnp.asarray(rng.normal(size=(DEVS, 33, 7))
                                 .astype(np.float32)),
                "b": jnp.asarray(rng.normal(size=(DEVS, 129))
                                 .astype(np.float32))}

    def _sync(mesh, plan, tree):
        from jax.sharding import PartitionSpec as P
        body = lambda t: sync_tree(t, plan, mesh, ("data",))
        f = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"))
        return jax.jit(f)(tree)

    @pytest.mark.parametrize("wire", ["bf16", "int8"])
    def test_fused_pack_bitwise_equals_unfused(mesh, wire):
        """flatten_buckets_fused + wire_all_reduce_fused must reproduce
        the seed path BIT-IDENTICALLY (cast commutes with concat; bucket
        absmax == max of per-leaf maxes) — the planner's alpha-beta model
        and the drift report see the same wire bytes either way."""
        tree = _tree(1)
        base = _sync(mesh, CommsPlan(schedule="ring", wire_dtype=wire,
                                     bucket_bytes=256, fused="off"), tree)
        fusd = _sync(mesh, CommsPlan(schedule="ring", wire_dtype=wire,
                                     bucket_bytes=256, fused="on"), tree)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(base[k]),
                                          np.asarray(fusd[k]))

    def test_fused_auto_follows_kernel_dispatch(monkeypatch):
        monkeypatch.delenv("REPRO_KERNELS", raising=False)
        assert not CommsPlan(wire_dtype="int8").fused_active()  # CPU: ref
        monkeypatch.setenv("REPRO_KERNELS", "interpret")
        assert CommsPlan(wire_dtype="int8").fused_active()
        assert not CommsPlan(wire_dtype=None).fused_active()

    def test_fused_flatten_absmax_matches_bucket_absmax():
        tree = _tree(2)
        plan = bucketer.plan_buckets(tree, 256)
        buckets = bucketer.flatten_buckets(plan, tree)
        fbuckets, absmaxes = bucketer.flatten_buckets_fused(plan, tree,
                                                            "int8")
        for b, fb, am in zip(buckets, fbuckets, absmaxes):
            np.testing.assert_array_equal(np.asarray(b), np.asarray(fb))
            assert float(am) == float(jnp.max(jnp.abs(b)))
