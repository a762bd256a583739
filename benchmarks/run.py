"""Benchmark harness — one section per paper table/claim.

  table1        Table 1: weak/strong scaling, hybrid vs pure DP
  gemm          §3.2: distributed GEMM across layout pairs (8 fake devices)
  precision     §4.2: half-storage numerics + at-par training
  pipeline      §2.2: auto-tuned data pipeline
  compression   Table 1 CNTK column: 1-bit/int8 EF gradients (8 fake devices)
  collectives   repro.comms schedules: measured vs cost-model (8 fake devices)
  pipeline_parallel  repro.pipeline: measured vs predicted bubble fraction
                and stage-boundary bytes (8 fake devices)
  memory_model  core/memory per-stage footprint vs compiled
                memory_analysis(); 1F1B ring vs all-M stash (8 fake devices)
  step_metrics  repro.obs: instrumented train run -> JSONL stream +
                BENCH_step_metrics.json drift snapshot (8 fake devices)
  calibrate     repro.core.calibrate: measure -> fit -> re-plan ->
                re-measure; asserts drift shrinks to n_flagged == 0 and
                commits experiments/calibration.json (8 fake devices)
  kernels       Pallas kernels (interpret) vs oracles
  serve_saturation  repro.serve continuous batching: offered-load sweep
                (req/s, TTFT, per-token p50/p99, pool utilization,
                preemptions, structured refusals) ->
                experiments/serve_saturation.json
  fault_drill   repro.faults + train/resilience: every injectable fault
                injected once into train + serve runs; FAILS unless all
                are recovered -> experiments/fault_drill.json
                (8 fake devices)
  roofline      §Roofline summary from the dry-run artifacts (if present)

Prints ``name,us_per_call,derived`` CSV.  Every section is CPU emulation:
this harness sets ``JAX_PLATFORMS=cpu`` for itself and its children, even
on a TPU host, so none of its numbers is a chip number (``chip_smoke.py``
is what runs on the chip).  Multi-device sections re-exec in a child with
8 fake host devices.
"""

from __future__ import annotations

import os
import subprocess
import sys

# before any section imports jax: this process and its children stay on
# the CPU (a parent holding a TPU would also starve the children of it)
os.environ["JAX_PLATFORMS"] = "cpu"

MULTIDEV = {"gemm": "benchmarks.gemm_layouts",
            "compression": "benchmarks.compression_bench",
            "collectives": "benchmarks.collectives_bench",
            "pipeline_parallel": "benchmarks.pipeline_parallel_bench",
            "memory_model": "benchmarks.memory_model_bench",
            "step_metrics": "benchmarks.step_metrics_bench",
            "calibrate": "benchmarks.calibrate_bench",
            "fault_drill": "benchmarks.fault_drill_bench",
            "table1": "benchmarks.table1"}
LOCAL = {"precision": "benchmarks.precision_bench",
         "pipeline": "benchmarks.pipeline_bench",
         "kernels": "benchmarks.kernels_bench",
         "serve_saturation": "benchmarks.serve_saturation_bench"}


def _run_child(module: str) -> int:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    r = subprocess.run([sys.executable, "-m", module], env=env,
                       capture_output=True, text=True, timeout=1800)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-3000:])
        print(f"{module},0.0,FAILED")
    return r.returncode


def _roofline_summary():
    import json
    path = "experiments/roofline.json"
    if not os.path.exists(path):
        print("roofline/missing,0.0,run launch.dryrun --all first")
        return
    rows = json.load(open(path))
    for r in rows:
        if r["mesh"] != "16x16":
            continue
        print(f"roofline/{r['arch']}_{r['shape']},"
              f"{1e6 * max(r['t_compute_s'], r['t_memory_s'], r['t_collective_s']):.0f},"
              f"dom={r['dominant']};frac={r['roofline_fraction']:.3f};"
              f"useful={r['useful_ratio']:.3f}")


def main(sections=None) -> None:
    sections = sections or list(LOCAL) + list(MULTIDEV) + ["roofline"]
    failures = 0
    for name in sections:
        if name in LOCAL:
            mod = __import__(LOCAL[name], fromlist=["main"])
            mod.main()
        elif name in MULTIDEV:
            failures += 1 if _run_child(MULTIDEV[name]) else 0
        elif name == "roofline":
            _roofline_summary()
    if failures:
        raise SystemExit(f"{failures} benchmark sections failed")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("sections", nargs="*", default=None)
    args = ap.parse_args()
    main(args.sections or None)
