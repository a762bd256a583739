"""Memory-model benchmark: predicted per-stage bytes vs compiled
``memory_analysis()`` on the 8-fake-device CPU mesh.

Run inside a child with XLA_FLAGS=--xla_force_host_platform_device_count=8
(benchmarks/run.py section ``memory_model`` does this).  Three comparisons:

- **baseline** — the non-pipelined microbatched train step: predicted
  (params + ZeRO optimizer + grads + activations + logits) vs the compiled
  peak.
- **gpipe / 1f1b** — the DP=2 x PP=2 pipelined step per schedule: the
  model's schedule-dependent terms (all-M tick stash for GPipe, ring stash
  + recompute for 1F1B) vs each compiled peak.
- **1f1b ring vs all-M stash** — the same cell compiled twice, once with
  the default min(M, 2S-1) ring and once with ``stash_slots=M`` (the
  historical all-M stash): the measured delta is 1F1B's realized memory
  win, and the model must predict its sign and ballpark.

CSV columns: name, us_per_call(=0, compile-only), derived
(pred vs meas bytes | ratio).  A JSON artifact lands in
``experiments/memory_model.json`` so CI can track the predicted-vs-
measured gap per PR.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import numpy as np
from jax.sharding import Mesh

from benchmarks.bench_util import emit
from repro.configs.base import ModelConfig
from repro.core import memory as mem_mod
from repro.core.planner import plan_for
from repro.models import Model
from repro.pipeline import pipeline_state_sds, pipeline_state_shardings
from repro.train import AdamWConfig, build_pipeline_train_step, build_train_step
from repro.train.step import state_sds, state_shardings

TINY = ModelConfig(name="mem-bench", family="dense", n_layers=4,
                   d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, vocab_size=128)

B, SEQ, M = 16, 32, 8
M_BASE = 4        # non-pipelined microbatches split the GLOBAL batch: each
                  # microbatch must still span the 4-way data axis


def _batch_sds():
    tok = jax.ShapeDtypeStruct((B, SEQ), np.int32)
    return {"tokens": tok, "labels": tok}


_measured_peak = mem_mod.compiled_peak_bytes   # shared measured-side formula


def _compile_pipelined(model, mesh, adamw, spec):
    ts = build_pipeline_train_step(model, mesh, adamw, pipeline=spec)
    sds = pipeline_state_sds(model, mesh, spec, adamw)
    sh = pipeline_state_shardings(model, mesh, spec, adamw)
    return jax.jit(ts, in_shardings=(sh, None),
                   donate_argnums=(0,)).lower(sds, _batch_sds()).compile()


def main():
    devs = np.array(jax.devices()[:4]).reshape(2, 2, 1)
    mesh = Mesh(devs, ("data", "pipe", "model"))
    base_mesh = Mesh(devs.reshape(4, 1), ("data", "model"))
    adamw = AdamWConfig(lr=1e-3, weight_decay=0.0)
    rows = []

    def record(name, pred, meas):
        ratio = pred / max(1, meas)
        emit(f"memory_model_{name}", 0.0,
             f"pred={pred / 1024:.0f}KB meas={meas / 1024:.0f}KB "
             f"ratio={ratio:.2f}")
        rows.append({"name": name, "predicted_bytes": int(pred),
                     "measured_bytes": int(meas), "ratio": round(ratio, 3)})

    # ---- non-pipelined baseline (DP=4, M microbatches) -------------------
    with jax.set_mesh(base_mesh):
        plan = plan_for(TINY, base_mesh)
        model = Model(TINY, base_mesh, plan, q_chunk=16, kv_chunk=16)
        ts = build_train_step(model, base_mesh, adamw,
                              num_microbatches=M_BASE)
        compiled = jax.jit(
            ts, in_shardings=(state_shardings(model, base_mesh, adamw), None),
            donate_argnums=(0,)).lower(
                state_sds(model, base_mesh, adamw), _batch_sds()).compile()
        pred = mem_mod.peak_stage_footprint(mem_mod.estimate_stage_footprints(
            TINY, local_batch=B // 4, seq_len=SEQ, num_microbatches=M_BASE,
            zero_shards=4)).total
        record("baseline_dp4", pred, _measured_peak(compiled))

    # ---- pipelined DP=2 x PP=2, both schedules ---------------------------
    with jax.set_mesh(mesh):
        plan = plan_for(TINY, mesh)
        model = Model(TINY, mesh, plan, q_chunk=16, kv_chunk=16)
        peaks = {}
        for sched in ("gpipe", "1f1b"):
            spec = dataclasses.replace(plan.pipeline, schedule=sched,
                                       num_microbatches=M)
            compiled = _compile_pipelined(model, mesh, adamw, spec)
            peaks[sched] = _measured_peak(compiled)
            pred = mem_mod.peak_stage_footprint(
                mem_mod.estimate_stage_footprints(
                    TINY, local_batch=B // 2, seq_len=SEQ, n_stages=2,
                    num_microbatches=M, schedule=sched, zero_shards=2)).total
            record(f"{sched}_S2_M{M}", pred, peaks[sched])

        # ---- 1F1B ring (min(M, 2S-1) slots) vs the all-M stash -----------
        spec_ring = dataclasses.replace(plan.pipeline, schedule="1f1b",
                                        num_microbatches=M)
        spec_allm = dataclasses.replace(spec_ring, stash_slots=M)
        meas_allm = _measured_peak(
            _compile_pipelined(model, mesh, adamw, spec_allm))
        meas_ring = peaks["1f1b"]
        act = (B // 2 // M) * SEQ * TINY.d_model * 2
        pred_delta = (M - spec_ring.resolved_stash_slots()) * act
        record("1f1b_ring_vs_allM_delta", pred_delta,
               max(1, meas_allm - meas_ring))
        emit(f"memory_model_1f1b_stash_slots", 0.0,
             f"ring={spec_ring.resolved_stash_slots()} allM={M} "
             f"ring_peak={meas_ring / 1024:.0f}KB "
             f"allM_peak={meas_allm / 1024:.0f}KB")
        rows.append({"name": "1f1b_stash_peaks",
                     "ring_slots": spec_ring.resolved_stash_slots(),
                     "all_m_slots": M,
                     "ring_peak_bytes": int(meas_ring),
                     "all_m_peak_bytes": int(meas_allm)})

    os.makedirs("experiments", exist_ok=True)
    with open("experiments/memory_model.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
