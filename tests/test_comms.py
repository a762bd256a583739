"""repro.comms: schedule equivalence vs psum, bucketing, wire formats,
topology cost model, and the train-step comms gradient-sync path."""

import os
import subprocess
import sys

import pytest

DEVS = 8


def _in_child() -> bool:
    return os.environ.get("REPRO_COMMS_CHILD") == str(DEVS)


if not _in_child():
    import pytest

    @pytest.mark.slow
    def test_comms_subprocess():
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={DEVS}")
        env["REPRO_COMMS_CHILD"] = str(DEVS)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-x", __file__],
            env=env, capture_output=True, text=True, timeout=900)
        if r.returncode != 0:
            pytest.fail("child failed:\n" + r.stdout[-3000:]
                        + r.stderr[-2000:])
else:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.comms import (CommsPlan, flatten_buckets, plan_buckets,
                             sync_tree, topology_from_mesh,
                             unflatten_buckets, wire_all_reduce)
    from repro.comms import schedules as sched_mod
    from repro.launch.mesh import make_mesh

    @pytest.fixture(scope="module")
    def mesh():
        return make_mesh((2, 4), ("data", "model"))

    def _run(mesh, body, x):
        return jax.jit(jax.shard_map(
            body, check_vma=False, mesh=mesh,
            in_specs=(P("data"),), out_specs=P("data")))(x)

    # ------------------------------------------------------------------
    # schedule equivalence with jax.lax.psum (>=4-device reduce groups)
    # ------------------------------------------------------------------

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                           (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("schedule", ["ring", "rsag", "tree"])
    def test_schedule_matches_psum(mesh, schedule, dtype, tol):
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 24)).astype(dtype)
        got = _run(mesh, lambda lx: sched_mod.all_reduce(
            lx, ("model",), schedule), x)
        want = _run(mesh, lambda lx: jax.lax.psum(lx, "model"), x)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol * 8)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                           (jnp.bfloat16, 2e-2)])
    def test_hierarchical_matches_psum(mesh, dtype, tol):
        """Two-level all-reduce over the full 8-device mesh: intranode
        ("model", size 4) first, then internode ("data", size 2)."""
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 24)).astype(dtype)
        got = _run(mesh, lambda lx: sched_mod.hierarchical_all_reduce(
            lx, "model", "data", 4), x)
        want = _run(mesh, lambda lx: jax.lax.psum(lx, ("data", "model")), x)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol * 16)

    def test_ring_odd_sizes_pad(mesh):
        """Local size not divisible by the group: padding must round-trip."""
        x = jnp.arange(2 * 7 * 5, dtype=jnp.float32).reshape(2, 7, 5)
        got = _run(mesh, lambda lx: sched_mod.ring_all_reduce(
            lx, "model", 4), x)
        want = _run(mesh, lambda lx: jax.lax.psum(lx, "model"), x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    # ------------------------------------------------------------------
    # wire formats
    # ------------------------------------------------------------------

    def test_bf16_wire_within_tolerance(mesh):
        x = jax.random.normal(jax.random.PRNGKey(2), (16, 16))
        got = _run(mesh, lambda lx: wire_all_reduce(
            lx, ("model",), "ring", "bf16"), x)
        want = _run(mesh, lambda lx: jax.lax.psum(lx, "model"), x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-1)

    def test_int8_wire_within_tolerance(mesh):
        x = jax.random.normal(jax.random.PRNGKey(3), (16, 16))
        got = _run(mesh, lambda lx: wire_all_reduce(
            lx, ("model",), "rsag", "int8"), x)
        want = np.asarray(_run(mesh, lambda lx: jax.lax.psum(lx, "model"), x))
        # absmax affine quantization: error bounded by n * scale/2
        atol = 4 * np.abs(want).max() / 127
        np.testing.assert_allclose(np.asarray(got), want, atol=atol)

    # ------------------------------------------------------------------
    # bucketer
    # ------------------------------------------------------------------

    def test_bucketer_roundtrip_exact(mesh):
        tree = {"a": jnp.arange(7, dtype=jnp.float32),
                "b": jnp.ones((3, 5), jnp.bfloat16) * 2,
                "c": {"d": jnp.full((11, 2), 3.0),
                      "e": jnp.arange(600, dtype=jnp.float32)}}
        plan = plan_buckets(tree, bucket_bytes=256)
        out = unflatten_buckets(plan, flatten_buckets(plan, tree))
        got_l, want_l = jax.tree.leaves(out), jax.tree.leaves(tree)
        for g, w in zip(got_l, want_l):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))

    def test_bucketer_deterministic_and_bounded(mesh):
        tree = [jnp.zeros((n,), jnp.float32) for n in (3, 9, 31, 5, 700, 2)]
        p1 = plan_buckets(tree, bucket_bytes=128)
        p2 = plan_buckets(tree, bucket_bytes=128)
        assert p1.bucket_sizes == p2.bucket_sizes
        assert [s.bucket for s in p1.slots] == [s.bucket for s in p2.slots]
        # every bucket except oversized single-leaf ones fits the budget
        for b, size in enumerate(p1.bucket_sizes):
            leaves_in = [s for s in p1.slots if s.bucket == b]
            if len(leaves_in) > 1:
                assert size * 4 <= 128
        # oversized leaf (700 floats) got its own bucket
        big = [s for s in p1.slots if s.size == 700]
        assert len([s for s in p1.slots
                    if s.bucket == big[0].bucket]) == 1

    def test_small_tensors_coalesce(mesh):
        """The point of bucketing: many tiny tensors -> few collectives."""
        tree = [jnp.zeros((8,), jnp.float32) for _ in range(100)]
        plan = plan_buckets(tree, bucket_bytes=1024)
        assert plan.num_buckets <= 4      # 100 tensors, ~4 buckets

    # ------------------------------------------------------------------
    # topology cost model
    # ------------------------------------------------------------------

    def test_topology_split_and_cost_model(mesh):
        topo = topology_from_mesh(mesh)
        assert topo.intra_axes == ("model",) and topo.inter_axes == ("data",)
        assert topo.intra_size == 4 and topo.inter_size == 2
        # latency-bound small messages -> tree; big ones -> hierarchical
        assert topo.best_schedule(1 * 1024) == "tree"
        assert topo.best_schedule(256 * 1024 * 1024) == "hier"
        # hierarchical beats flat ring once internode bandwidth dominates
        big = 64 * 1024 * 1024
        assert topo.allreduce_time(big, "hier") < topo.allreduce_time(
            big, "ring")

    def test_planner_attaches_comms_plan(mesh):
        from repro.configs.base import ModelConfig
        from repro.core.planner import plan_for

        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                          vocab_size=64)
        plan = plan_for(cfg, mesh)
        assert plan.comms is not None
        assert plan.comms.schedule in ("psum", "ring", "rsag", "tree", "hier")

    # ------------------------------------------------------------------
    # train-step integration
    # ------------------------------------------------------------------

    def _tiny_setup(dp_mesh):
        from repro.configs.base import ModelConfig
        from repro.core.planner import plan_for
        from repro.models import Model
        from repro.train import init_state

        cfg = ModelConfig(name="comms-tiny", family="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                          d_ff=128, vocab_size=64)
        model = Model(cfg, dp_mesh, plan_for(cfg, dp_mesh),
                      q_chunk=16, kv_chunk=16)
        st = init_state(model, dp_mesh, jax.random.PRNGKey(0))
        state = {"params": st.params, "opt": st.opt}
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        batch = {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}
        return model, state, batch

    def test_train_step_bucketed_compressed_matches_fp32(mesh):
        """Acceptance: bucketed + bf16-compressed gradient sync through
        repro.comms matches the unbucketed fp32 GSPMD path within bf16
        tolerance (4-way DP mesh)."""
        from repro.train import build_train_step

        dp_mesh = make_mesh((4, 1), ("data", "model"))
        with jax.set_mesh(dp_mesh):
            model, state, batch = _tiny_setup(dp_mesh)
            base = jax.jit(build_train_step(model, dp_mesh))
            s_ref, m_ref = base(jax.tree.map(lambda x: x, state), batch)

            plan = CommsPlan(schedule="ring", wire_dtype="bf16",
                             bucket_bytes=16 * 1024)   # forces many buckets
            step = jax.jit(build_train_step(model, dp_mesh, comms=plan))
            s_got, m_got = step(jax.tree.map(lambda x: x, state), batch)

        assert abs(float(m_got["loss"]) - float(m_ref["loss"])) < 2e-2
        for g, w in zip(jax.tree.leaves(s_got["params"]),
                        jax.tree.leaves(s_ref["params"])):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("schedule,wire", [("hier", None),
                                               ("rsag", "int8"),
                                               ("auto", "bf16")])
    def test_train_step_all_schedules(mesh, schedule, wire):
        from repro.train import build_train_step

        dp_mesh = make_mesh((4, 1), ("data", "model"))
        with jax.set_mesh(dp_mesh):
            model, state, batch = _tiny_setup(dp_mesh)
            base = jax.jit(build_train_step(model, dp_mesh))
            s_ref, _ = base(jax.tree.map(lambda x: x, state), batch)
            plan = CommsPlan(schedule=schedule, wire_dtype=wire,
                             bucket_bytes=64 * 1024)
            step = jax.jit(build_train_step(model, dp_mesh, comms=plan))
            s_got, _ = step(jax.tree.map(lambda x: x, state), batch)
        for g, w in zip(jax.tree.leaves(s_got["params"]),
                        jax.tree.leaves(s_ref["params"])):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                rtol=3e-2, atol=3e-2)

    def test_train_step_comms_rejects_tp(mesh):
        """The explicit path is DP-only: a TP mesh must raise."""
        from repro.train import build_train_step

        with jax.set_mesh(mesh):
            model, _, _ = _tiny_setup(mesh)
            with pytest.raises(ValueError, match="data-parallel"):
                build_train_step(model, mesh, comms=CommsPlan())

    # ------------------------------------------------------------------
    # sync_tree semantics
    # ------------------------------------------------------------------

    def test_sync_tree_is_pmean(mesh):
        x = jax.random.normal(jax.random.PRNGKey(5), (16, 8))
        plan = CommsPlan(schedule="hier", bucket_bytes=128)
        got = _run(mesh, lambda lx: sync_tree(
            {"g": lx}, plan, mesh, ("data", "model"))["g"], x)
        want = _run(mesh, lambda lx: jax.lax.pmean(lx, ("data", "model")), x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
