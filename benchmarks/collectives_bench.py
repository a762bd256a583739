"""Collective-schedule benchmark: measured vs cost-model time per schedule.

Run inside a child process with XLA_FLAGS=--xla_force_host_platform_device_count=8
(benchmarks/run.py section ``collectives`` does this).  For each message
size x schedule it times one all-reduce over the mesh and prints the
alpha-beta prediction from :mod:`repro.comms.topology` alongside, plus the
bucketed/compressed gradient-sync path end to end.

CSV columns: name, us_per_call, derived (predicted us | wire format).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.bench_util import emit, time_fn
from benchmarks.hlo_cost import (allreduce_wire_bytes, analyze_text,
                                 collective_seconds)
from repro.comms import (CommsPlan, sync_tree, topology_from_mesh,
                         wire_all_reduce)
from repro.comms.topology import SCHEDULES

SIZES = {"256KB": 64 * 1024, "4MB": 1024 * 1024, "32MB": 8 * 1024 * 1024}


def _mesh():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _reduce_fn(mesh, schedule, wire=None):
    axes = ("data", "model")

    def body(lx):
        return wire_all_reduce(lx, axes, schedule, wire)

    return jax.jit(jax.shard_map(body, check_vma=False, mesh=mesh,
                                 in_specs=(P(),), out_specs=P()))


def main():
    mesh = _mesh()
    topo = topology_from_mesh(mesh)
    n = topo.world_size

    for size_name, elems in SIZES.items():
        x = jnp.arange(elems, dtype=jnp.float32) / elems
        nbytes = elems * 4
        for sched in SCHEDULES:
            fn = _reduce_fn(mesh, sched)
            us = time_fn(fn, x, iters=5)
            pred = topo.allreduce_time(nbytes, sched, n) * 1e6
            wire = allreduce_wire_bytes(nbytes, n, sched,
                                        intra_size=topo.intra_size)
            emit(f"allreduce_{sched}_{size_name}", us,
                 f"pred={pred:.1f}us wire={wire / 1024:.0f}KB")

    # cross-check: walk the compiled psum HLO with the structural cost
    # analyzer and price its collectives on the same topology
    x = jnp.arange(SIZES["4MB"], dtype=jnp.float32)
    hlo = _reduce_fn(mesh, "psum").lower(x).compile().as_text()
    cost = analyze_text(hlo)
    emit("hlo_walker_psum_4MB", collective_seconds(cost, topo, n) * 1e6,
         f"coll_wire={cost.coll_wire / 1024:.0f}KB "
         f"counts={sum(cost.coll_counts.values()):.0f}")

    # wire formats on the bandwidth-optimal schedule
    x = jnp.arange(SIZES["4MB"], dtype=jnp.float32) / SIZES["4MB"]
    for wire in ("bf16", "int8"):
        fn = _reduce_fn(mesh, "ring", wire)
        us = time_fn(fn, x, iters=5)
        emit(f"allreduce_ring_4MB_{wire}", us, f"wire={wire}")

    # bucketed gradient sync end to end (many small tensors -> few buckets)
    grads = {f"w{i}": jnp.ones((64, 64), jnp.float32) * i for i in range(24)}
    plan = CommsPlan(schedule="hier", wire_dtype="bf16",
                     bucket_bytes=128 * 1024)
    axes = ("data", "model")

    def sync_body(g):
        return sync_tree(g, plan, mesh, axes)

    fn = jax.jit(jax.shard_map(sync_body, check_vma=False, mesh=mesh,
                               in_specs=(P(),), out_specs=P()))
    us = time_fn(fn, grads, iters=5)
    emit("bucketed_sync_24x64x64_hier_bf16", us,
         f"pred={plan.estimate_seconds(mesh, 24 * 64 * 64 * 4) * 1e6:.1f}us")


if __name__ == "__main__":
    main()
