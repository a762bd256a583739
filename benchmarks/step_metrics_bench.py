"""Step-metrics benchmark: one instrumented training run end to end.

Run inside a child with XLA_FLAGS=--xla_force_host_platform_device_count=8
(benchmarks/run.py section ``step_metrics`` does this).  Exercises the
exact ``--metrics`` flow the train CLI ships: a pipelined (pp=2) run on
the fake-device mesh streams plan/compile/step spans, per-schedule comms
wire-bytes counters, and opcache/state gauges to a JSONL file, then
snapshots everything — plus the predicted-vs-measured drift report — into
``BENCH_step_metrics.json`` at the repo root (the per-PR perf-trajectory
artifact the ROADMAP's calibration loop consumes).

CSV columns: name, us_per_call, derived (the headline snapshot numbers).
"""

from __future__ import annotations

import json
import os

from benchmarks.bench_util import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSONL = os.path.join(ROOT, "experiments", "step_metrics.jsonl")
SNAPSHOT = os.path.join(ROOT, "BENCH_step_metrics.json")

ARCH = "gemma-2b"
STEPS = 8


def main():
    from repro.launch.train import run

    os.makedirs(os.path.dirname(JSONL), exist_ok=True)
    if os.path.exists(JSONL):
        os.remove(JSONL)
    run(ARCH, steps=STEPS, batch=16, seq=32, scale_down=64,
        microbatches=4, pp=2, log_every=STEPS,
        metrics=JSONL, metrics_snapshot=SNAPSHOT)

    snap = json.load(open(SNAPSHOT))
    m = snap["metrics"]
    step = m["histograms"]["span.step.s"]
    emit(f"step_metrics_{ARCH}_step", step["p50"] * 1e6,
         f"n={step['count']} p99={step['p99'] * 1e6:.0f}us")
    for name in ("span.plan.s", "span.compile.s"):
        h = m["histograms"].get(name)
        if h and h["count"]:
            emit(f"step_metrics_{name}", h["mean"] * 1e6, f"n={h['count']}")
    wire = m["counters"].get("comms.wire_bytes", 0)
    emit("step_metrics_comms_wire", 0.0, f"bytes_per_step={wire}")
    g = m["gauges"]
    emit("step_metrics_peak", 0.0,
         f"pred={g.get('memory.predicted_peak_bytes', 0) / 2**20:.1f}MB "
         f"meas={g.get('memory.measured_peak_bytes', 0) / 2**20:.1f}MB")
    if "pipeline.bubble.measured" in g:
        emit("step_metrics_bubble", 0.0,
             f"pred={g['pipeline.bubble.predicted']:.3f} "
             f"meas={g['pipeline.bubble.measured']:.3f}")
    drift = snap["meta"].get("drift", {})
    emit("step_metrics_drift", 0.0,
         f"rows={len(drift.get('rows', []))} "
         f"flagged={drift.get('n_flagged', 0)}")


if __name__ == "__main__":
    main()
