"""Pipeline-parallel benchmark: measured vs cost-model bubble fraction and
stage-boundary wire bytes.

Run inside a child with XLA_FLAGS=--xla_force_host_platform_device_count=8
(benchmarks/run.py section ``pipeline_parallel`` does this).  A tiny dense
transformer trains on a (data=2, pipe=2, model=1) mesh under both
schedules; for each microbatch count M we report

- step wall time,
- predicted bubble (S-1)/(M+S-1) from ``repro.pipeline.costs``,
- measured bubble 1 - M*t_mb/t(M), with the per-microbatch time t_mb
  taken from the slope between the two largest M (bubble-free estimate),

and a structural cross-check: the compiled step's collective-permute wire
bytes (``hlo_cost`` walker) against the cost model's stage-boundary
formula.

CSV columns: name, us_per_call, derived (pred vs meas | bytes).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from benchmarks.bench_util import emit, time_fn
from benchmarks.hlo_cost import (analyze_text, pipeline_boundary_wire_bytes,
                                 pipeline_bubble_fraction)
from repro.configs.base import ModelConfig
from repro.core.planner import plan_for
from repro.models import Model
from repro.pipeline import boundary_act_bytes, pipeline_init_state
from repro.train import AdamWConfig, build_pipeline_train_step

TINY = ModelConfig(name="pp-bench", family="dense", n_layers=4,
                   d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, vocab_size=128)

B, S_SEQ = 16, 32
MICROBATCHES = (2, 4, 8)


def _mesh():
    devs = np.array(jax.devices()[:4]).reshape(2, 2, 1)
    return Mesh(devs, ("data", "pipe", "model"))


def _batch():
    rng = np.random.RandomState(0)
    toks = rng.randint(0, TINY.vocab_size, (B, S_SEQ + 1)).astype(np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:])}


def main():
    mesh = _mesh()
    batch = _batch()
    adamw = AdamWConfig(lr=1e-3, weight_decay=0.0)
    with jax.set_mesh(mesh):
        plan = plan_for(TINY, mesh)
        model = Model(TINY, mesh, plan, q_chunk=16, kv_chunk=16)
        n_stages = plan.pipeline.n_stages
        local_b = B // mesh.shape["data"]

        for sched in ("gpipe", "1f1b"):
            times = {}
            for m in MICROBATCHES:
                spec = dataclasses.replace(plan.pipeline, schedule=sched,
                                           num_microbatches=m)
                ts = jax.jit(build_pipeline_train_step(
                    model, mesh, adamw, pipeline=spec))
                state = pipeline_init_state(model, mesh, spec,
                                            jax.random.PRNGKey(0))
                # time the step without donation churn: rebuild state args
                times[m] = time_fn(lambda st=state: ts(st, batch)[1],
                                   warmup=2, iters=5)
            # bubble-free per-microbatch time from the slope of the two
            # largest M (the bubble term cancels in the difference)
            m_hi, m_lo = MICROBATCHES[-1], MICROBATCHES[-2]
            t_mb = max(1e-9, (times[m_hi] - times[m_lo]) / (m_hi - m_lo))
            for m in MICROBATCHES:
                pred = pipeline_bubble_fraction(n_stages, m)
                meas = 1.0 - m * t_mb / times[m]
                emit(f"pipeline_{sched}_S{n_stages}_M{m}", times[m],
                     f"pred_bubble={pred:.3f} meas_bubble={meas:.3f}")

        # structural cross-check: collective-permute wire bytes in the
        # compiled HLO vs the cost-model boundary formula
        m = MICROBATCHES[0]
        spec = dataclasses.replace(plan.pipeline, schedule="gpipe",
                                   num_microbatches=m)
        ts = jax.jit(build_pipeline_train_step(model, mesh, adamw,
                                               pipeline=spec))
        state = pipeline_init_state(model, mesh, spec, jax.random.PRNGKey(0))
        hlo = ts.lower(state, batch).compile().as_text()
        cost = analyze_text(hlo)
        walked = cost.coll_by_op.get("collective-permute", 0.0)
        act = boundary_act_bytes(local_b // m, S_SEQ, TINY.d_model)
        pred_bytes = pipeline_boundary_wire_bytes(act, n_stages, m)
        emit(f"pipeline_boundary_bytes_S{n_stages}_M{m}", 0.0,
             f"pred={pred_bytes / 1024:.0f}KB walked={walked / 1024:.0f}KB")


if __name__ == "__main__":
    main()
