"""Calibration-loop benchmark: measure -> fit -> re-plan -> re-measure.

Run inside a child with XLA_FLAGS=--xla_force_host_platform_device_count=8
(benchmarks/run.py section ``calibrate`` does this).  Closes the loop the
ROADMAP's self-calibrating planner asked for, on the same pp=2 gemma-2b
cell the ``step_metrics`` section commits:

1. **measure** — an uncalibrated instrumented train run (baseline drift
   snapshot), plus measured single collectives at several sizes/schedules
   recorded as ``collective_sample`` events (the link fit's regression
   rows);
2. **fit** — :func:`repro.core.calibrate.fit_from_files` least-squares
   refits link alpha/beta, pipeline tick/intercept (-> step overhead),
   effective device FLOPs, and the memory scale; the table lands in
   ``experiments/calibration.json`` with provenance + residuals;
3. **re-plan / re-measure** — the same cell re-runs under
   ``--calibration``; its drift snapshot (now predicted with fitted
   constants) overwrites the committed ``BENCH_step_metrics.json``;
4. **assert** — calibrated drift must shrink vs baseline on every joined
   metric and ``n_flagged`` must be 0 under the tightened tolerances
   (``repro.obs.report.DEFAULT_TOLERANCES``), else the section fails.

CSV columns: name, us_per_call, derived (drift before/after, constants).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.bench_util import emit, time_fn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXP = os.path.join(ROOT, "experiments")
BASE_JSONL = os.path.join(EXP, "calibration_baseline.jsonl")
BASE_SNAP = os.path.join(EXP, "calibration_baseline.json")
TABLE = os.path.join(EXP, "calibration.json")
CAL_JSONL = os.path.join(EXP, "step_metrics.jsonl")
SNAPSHOT = os.path.join(ROOT, "BENCH_step_metrics.json")

# The committed step_metrics cell (benchmarks/step_metrics_bench.py).
ARCH = "gemma-2b"
STEPS = 8
CELL = dict(batch=16, seq=32, scale_down=64, microbatches=4, pp=2)

#: collective-probe sizes (bytes): small enough to stay fast on the CPU
#: simulator, spread enough to separate alpha (latency) from beta (bytes).
PROBE_SIZES = (256 * 1024, 1024 * 1024, 4 * 1024 * 1024)
PROBE_SCHEDULES = ("psum", "ring", "tree")


def _measure_collectives(obs) -> None:
    """Time one all-reduce per (size, schedule) on the 8-device mesh and
    record each as a ``collective_sample`` event whose (steps, wire_bytes)
    regression row comes from the cost model's own design
    (:func:`repro.comms.topology.allreduce_design`)."""
    from repro.comms import wire_all_reduce
    from repro.comms.topology import allreduce_design

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    n = 8
    axes = ("data", "model")
    for nbytes in PROBE_SIZES:
        elems = nbytes // 4
        x = jnp.arange(elems, dtype=jnp.float32) / elems
        for sched in PROBE_SCHEDULES:
            fn = jax.jit(jax.shard_map(
                lambda lx, s=sched: wire_all_reduce(lx, axes, s),
                check_vma=False, mesh=mesh, in_specs=(P(),), out_specs=P()))
            us = time_fn(fn, x, warmup=2, iters=5)
            steps, wire = allreduce_design(nbytes, sched, n)
            obs.event("collective_sample", schedule=sched, nbytes=nbytes,
                      n=n, steps=steps, wire_bytes=wire, seconds=us / 1e6)
            emit(f"calibrate_probe_{sched}_{nbytes >> 10}KB", us,
                 f"steps={steps} wire={wire / 1024:.0f}KB")


def _drift_rows(snap_path: str) -> dict:
    snap = json.load(open(snap_path))
    return {r["name"]: r for r in
            snap["meta"].get("drift", {}).get("rows", [])}


def main():
    from repro import obs as obs_mod
    from repro.core import calibrate
    from repro.launch.train import run

    os.makedirs(EXP, exist_ok=True)
    for p in (BASE_JSONL, CAL_JSONL):
        if os.path.exists(p):
            os.remove(p)

    # 1a. baseline instrumented run (uncalibrated constants)
    run(ARCH, steps=STEPS, log_every=STEPS, metrics=BASE_JSONL,
        metrics_snapshot=BASE_SNAP, **CELL)

    # 1b. measured collectives appended to the same stream (the JSONL sink
    # appends, so the fitter sees one self-contained baseline file)
    obs = obs_mod.Obs(jsonl=BASE_JSONL, name="calibrate/collectives")
    try:
        _measure_collectives(obs)
    finally:
        obs.close()

    # 2. fit + persist
    table = calibrate.fit_from_files([BASE_JSONL], snapshot_path=BASE_SNAP)
    table.save(TABLE)
    print(f"fitted: {table.describe()}")
    for w in table.provenance.get("warnings", []):
        print(f"  warning [{w['field']}]: {w['reason']}")
    if table.device_flops is None or table.inter is None:
        raise SystemExit("calibrate: fit fell back to defaults on the "
                         "bench cell — cannot close the loop")
    emit("calibrate_fitted_flops", 0.0,
         f"{table.device_flops / 1e9:.3f}GFLOPs/s "
         f"overhead={table.step_overhead_s * 1e3:.1f}ms "
         f"mem_scale={table.memory_scale:.3f}")

    # 3. re-plan + re-measure under the fitted table; this snapshot is the
    # committed perf-trajectory artifact
    run(ARCH, steps=STEPS, log_every=STEPS, metrics=CAL_JSONL,
        metrics_snapshot=SNAPSHOT, calibration=TABLE, **CELL)

    # 4. drift must shrink, and nothing may stay flagged
    base = _drift_rows(BASE_SNAP)
    cal = _drift_rows(SNAPSHOT)
    n_flagged = json.load(open(SNAPSHOT))["meta"]["drift"]["n_flagged"]
    worse = []
    for name in sorted(set(base) & set(cal)):
        b, c = abs(base[name]["drift"]), abs(cal[name]["drift"])
        emit(f"calibrate_drift_{name}", 0.0,
             f"before={b:.3f} after={c:.3f}")
        if c > max(b, cal[name]["tolerance"]):
            worse.append(f"{name}: |drift| {b:.3f} -> {c:.3f}")
    if worse:
        raise SystemExit("calibrate: drift grew after calibration: "
                         + "; ".join(worse))
    if n_flagged:
        flagged = [r["name"] for r in cal.values() if r["flagged"]]
        raise SystemExit(f"calibrate: {n_flagged} metric(s) still flagged "
                         f"after calibration: {flagged}")
    emit("calibrate_loop", 0.0, f"n_flagged={n_flagged} table={TABLE}")


if __name__ == "__main__":
    main()
