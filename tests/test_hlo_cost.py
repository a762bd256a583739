"""Calibration tests for the structural HLO cost walker.

The roofline depends on this walker being right; each test pins one of
its accounting rules against a program with known cost.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.hlo_cost import analyze_text

M = 256


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_single_matmul_flops_exact():
    a = jnp.ones((M, M), jnp.float32)
    txt = _compile_text(lambda a, b: a @ b, a, a)
    cost = analyze_text(txt)
    assert abs(cost.flops - 2 * M**3) / (2 * M**3) < 0.01


def test_scan_trip_count_multiplied():
    """THE bug this walker exists for: cost_analysis counts while bodies
    once; the walker must multiply by the trip count."""
    def scanned(a, b):
        def body(c, _):
            return jnp.tanh(c @ b), ()
        out, _ = jax.lax.scan(body, a, None, length=5)
        return out

    a = jnp.ones((M, M), jnp.bfloat16)
    txt = _compile_text(scanned, a, a)
    cost = analyze_text(txt)
    expect = 5 * 2 * M**3
    assert abs(cost.flops - expect) / expect < 0.01
    # and the builtin is indeed wrong (counts once) — guards against a
    # future jax fixing this silently
    ca = jax.jit(scanned).lower(a, a).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):   # older jax returns [dict]
        ca = ca[0] if ca else {}
    assert ca.get("flops", 0) < 0.5 * expect


def test_nested_scan_trips_compound():
    def nested(a, b):
        def outer(c, _):
            def inner(d, _):
                return d @ b, ()
            d, _ = jax.lax.scan(inner, c, None, length=3)
            return d, ()
        out, _ = jax.lax.scan(outer, a, None, length=4)
        return out

    a = jnp.ones((M, M), jnp.float32)
    cost = analyze_text(_compile_text(nested, a, a))
    expect = 4 * 3 * 2 * M**3
    assert abs(cost.flops - expect) / expect < 0.01


def test_collective_wire_formulas():
    import os
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        sys.path.insert(0, %r)
        from benchmarks.hlo_cost import analyze_text

        mesh = jax.make_mesh((8,), ("m",),
                             axis_types=(jax.sharding.AxisType.Auto,))

        def f(x):
            return jax.shard_map(
                lambda lx: jax.lax.all_gather(lx, "m", axis=0, tiled=True),
                check_vma=False, mesh=mesh, in_specs=P("m"), out_specs=P())(x)

        l = jax.jit(f, in_shardings=NamedSharding(mesh, P("m"))).lower(
            jax.ShapeDtypeStruct((1024,), jnp.float32))
        cost = analyze_text(l.compile().as_text())
        expect = 1024 * 4 * 7 / 8          # result bytes x (n-1)/n
        assert abs(cost.coll_wire - expect) / expect < 0.01, cost.coll_wire
        assert cost.coll_counts.get("all-gather") == 1, cost.coll_counts
        print("WIRE_OK")
    """) % (str(__import__("os").path.join(
        __import__("os").path.dirname(__file__), "..")),)
    env = dict(__import__("os").environ)
    root = __import__("os").path.join(
        __import__("os").path.dirname(__file__), "..")
    env["PYTHONPATH"] = __import__("os").pathsep.join(
        [root, __import__("os").path.join(root, "src")]
        + env.get("PYTHONPATH", "").split(__import__("os").pathsep))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "WIRE_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


def test_allreduce_wire_bytes_formulas():
    """Per-schedule wire formulas match the textbook counts (and the
    schedules implemented in repro.comms.schedules)."""
    from benchmarks.hlo_cost import allreduce_wire_bytes as wire

    nb, n = 1024.0, 8
    assert wire(nb, n, "ring") == pytest.approx(2 * nb * 7 / 8)
    assert wire(nb, n, "rsag") == wire(nb, n, "ring") == wire(nb, n, "psum")
    assert wire(nb, n, "tree") == pytest.approx(nb * 3)        # log2(8)
    # two-level: intra RS+AG on full buffer + inter on the 1/4 slice
    inter_share = 2 * (nb / 4) * 1 / 2
    hier = wire(nb, n, "hier", intra_size=4)
    assert hier == pytest.approx(2 * nb * 3 / 4 + inter_share)
    # total bytes match the flat ring; the win is that only the 1/intra
    # slice crosses the slow internode link
    assert inter_share < wire(nb, n, "ring")
    assert wire(nb, 1, "ring") == 0.0
    with pytest.raises(ValueError):
        wire(nb, n, "nope")


def test_collective_seconds_alpha_beta():
    """Time estimate = wire/bandwidth + steps*latency on the slow link."""
    from benchmarks.hlo_cost import Cost, collective_seconds
    from repro.comms.topology import LinkSpec, Topology

    topo = Topology(intra_axes=("model",), inter_axes=("data",),
                    axis_sizes={"model": 4, "data": 2},
                    intra=LinkSpec(1e-6, 100e9),
                    inter=LinkSpec(10e-6, 10e9))
    cost = Cost(coll_wire=1e9, coll_counts={"all-reduce": 2,
                                            "all-gather": 1})
    got = collective_seconds(cost, topo)          # world n = 8
    want = 1e9 / 10e9 + (2 * (2 * 7) + 1 * 7) * 10e-6
    assert got == pytest.approx(want)


def test_fusion_bytes_at_boundary_only():
    """Fused elementwise chains count operand+result bytes once."""
    a = jnp.ones((M, M), jnp.float32)
    txt = _compile_text(lambda x: jnp.tanh(x * 2.0 + 1.0), a)
    cost = analyze_text(txt)
    # one fusion: read a (256KB) + write out (256KB) ~ 512KB (+ small temps)
    assert cost.hbm_bytes <= 3 * M * M * 4
