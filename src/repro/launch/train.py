"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

A thin CLI over :class:`repro.api.Session`: config -> ``Session.plan``
(mesh + parallel plan + memory fail-fast) -> ``Session.train_step`` (the
single dispatcher over the plain/ZeRO, comms, and pipeline paths) ->
checkpoint/restart loop on the session's persistent device-resident
state.  On this CPU container use reduced dims (--scale-down) and a small
mesh; on a fleet the same driver runs the production mesh (the dry-run
proves those shardings).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs as obs_mod
from repro.api import PlanMemoryError, Session
from repro.checkpoint import CheckpointManager
from repro.configs.base import scale_config  # noqa: F401  (legacy import site)
from repro.core import memory as mem_mod
from repro.data import Pipeline, Stage, SyntheticLM
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.obs import report as report_mod
from repro.train import AdamWConfig, ResilientStepLoop, StepTimeWatchdog, \
    warmup_cosine


def load_fault_plan(spec: Optional[str]):
    """``--faults``: a JSON file path or inline JSON — either a list of
    FaultSpec dicts or ``{"seed": ..., "specs": [...]}``."""
    if not spec:
        return None
    import json
    from repro.faults import FaultPlan, FaultSpec
    text = spec
    if os.path.exists(spec):
        with open(spec) as f:
            text = f.read()
    doc = json.loads(text)
    seed, specs = (doc.get("seed", 0), doc.get("specs", [])) \
        if isinstance(doc, dict) else (0, doc)
    return FaultPlan([FaultSpec(**d) for d in specs], seed=seed)


def validate_plan_memory(cfg, mesh, *, batch: int, seq: int,
                         microbatches: int, schedule: str,
                         hbm_gib: Optional[float] = None) -> None:
    """Fail fast when the memory model says the plan cannot fit.

    Kept as a standalone helper (``Session.plan`` folds the same check
    in): prices the cell against the per-device budget and raises the
    structured :class:`repro.api.PlanMemoryError` with the footprint
    table instead of letting the step OOM minutes into compilation.
    """
    budget = mem_mod.budget_for(mesh, hbm_gib=hbm_gib)
    fps = mem_mod.footprints_for_mesh(
        cfg, mesh, global_batch=batch, seq_len=seq,
        num_microbatches=microbatches, schedule=schedule)
    if not all(f.fits(budget) for f in fps):
        raise PlanMemoryError.for_cell(fps, budget)
    peak = mem_mod.peak_stage_footprint(fps)
    print(f"memory model: predicted peak {peak.total / mem_mod.GIB:.3f} "
          f"GiB/device vs {budget.describe()} -> fits")


def _measure_peak(session, plan, obs) -> None:
    """AOT-compile the plan's step (under a ``compile`` span) and publish
    the executable's per-device peak next to the memory model's — both
    the calibrated prediction (what the drift report judges) and the raw
    uncalibrated one (what the fitter regresses the scale from)."""
    lowered, _meta = session.dryrun(plan)
    with obs.span("compile", step="train_step", arch=plan.cfg.name):
        compiled = lowered.compile()
    peak = mem_mod.peak_stage_footprint(plan.footprints)
    obs.gauge(report_mod.MEASURED_PEAK_GAUGE).set(
        mem_mod.compiled_peak_bytes(compiled))
    obs.gauge(report_mod.PREDICTED_PEAK_GAUGE).set(
        float(peak.calibrated_total))
    obs.gauge(report_mod.PREDICTED_RAW_PEAK_GAUGE).set(float(peak.total))


def _measure_bubble(session, plan, batch, obs) -> None:
    """Microbatch-slope bubble probe (the pipeline_parallel benchmark's
    estimator): time non-donating steps at two microbatch counts with the
    MICROBATCH SIZE held fixed (the probe batch is sliced down to
    B*m/M rows, otherwise shrinking M grows the microbatches and the
    per-microbatch time t_mb is no longer a constant slope); the
    bubble-free t_mb is then the slope between the two counts and
    measured bubble at the plan's M is 1 - M*t_mb/t(M).  Publishes the
    measured/predicted pair the drift report joins on."""
    from repro.api.session import dispatch_train_step

    spec = plan.pipeline
    m_hi = spec.num_microbatches
    m_lo = m_hi // 2
    gb = plan.global_batch
    if m_hi < 2 or (gb * m_lo) % m_hi:
        return   # one microbatch: slope needs two distinct counts
    state = session.get("train_state")
    times = {}
    for m in (m_lo, m_hi):
        fn = jax.jit(dispatch_train_step(
            plan.model, session.mesh, adamw=plan.adamw,
            num_microbatches=m, comms=plan.comms,
            pipeline=dataclasses.replace(spec, num_microbatches=m),
            path=plan.path))
        b_m = jax.tree.map(lambda x: x[: gb * m // m_hi], batch)
        jax.block_until_ready(fn(state, b_m))   # compile
        jax.block_until_ready(fn(state, b_m))   # warm
        best = float("inf")
        for _ in range(5):                      # best-of-5: the slope is
            t0 = time.perf_counter()            # a difference of two Ms,
            jax.block_until_ready(fn(state, b_m))   # noise kills it
            best = min(best, time.perf_counter() - t0)
        times[m] = best
    meas = report_mod.measured_bubble_fraction(times)[m_hi]
    pred = report_mod.predicted_bubble_fraction(spec)
    obs.gauge(report_mod.MEASURED_BUBBLE_GAUGE).set(meas)
    obs.gauge(report_mod.PREDICTED_BUBBLE_GAUGE).set(pred)
    obs.event("bubble_probe", microbatches=sorted(times),
              times_s=[times[m] for m in sorted(times)], measured=meas,
              predicted=pred)


def run(arch: str, *, steps: int = 50, batch: int = 8, seq: int = 128,
        scale_down: int = 64, lr: float = 3e-3, microbatches: int = 1,
        ckpt_dir: Optional[str] = None, ckpt_every: int = 25,
        resume: bool = False, mesh=None, log_every: int = 10,
        seed: int = 0, comms: str = "auto", pp: int = 1,
        pp_schedule: str = "gpipe", hbm_gib: Optional[float] = None,
        metrics: Optional[str] = None,
        metrics_snapshot: Optional[str] = None,
        calibration: Optional[str] = None,
        resilient: bool = False, faults: Optional[str] = None):
    # Telemetry is strictly opt-in: without --metrics every obs call site
    # sees the NULL singleton, so numerics and stdout are bit-identical
    # to the uninstrumented driver.
    obs = obs_mod.Obs(jsonl=metrics, name=f"train/{arch}") if metrics \
        else obs_mod.NULL
    prev_obs = obs_mod.set_active(obs)
    # Calibrated planning is likewise opt-in and scoped to this run: the
    # fitted table becomes the process-wide active one before any plan or
    # topology is built, and the previous table is restored on exit.
    prev_cal = None
    if calibration:
        from repro.core import calibrate
        table = calibrate.load(calibration)
        prev_cal = calibrate.set_active(table)
        print(f"calibration: {table.describe()}  [{calibration}]")
    try:
        return _run(arch, obs, steps=steps, batch=batch, seq=seq,
                    scale_down=scale_down, lr=lr, microbatches=microbatches,
                    ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, resume=resume,
                    mesh=mesh, log_every=log_every, seed=seed, comms=comms,
                    pp=pp, pp_schedule=pp_schedule, hbm_gib=hbm_gib,
                    metrics=metrics, metrics_snapshot=metrics_snapshot,
                    calibration=calibration, resilient=resilient,
                    faults=faults)
    finally:
        if calibration:
            from repro.core import calibrate
            calibrate.set_active(prev_cal)
        obs_mod.set_active(prev_obs)
        obs.close()


def _run(arch: str, obs, *, steps, batch, seq, scale_down, lr, microbatches,
         ckpt_dir, ckpt_every, resume, mesh, log_every, seed, comms, pp,
         pp_schedule, hbm_gib, metrics, metrics_snapshot, calibration=None,
         resilient=False, faults=None):
    session = Session(mesh=mesh if mesh is not None
                      else mesh_mod.make_host_mesh(pp), hbm_gib=hbm_gib,
                      obs=obs)
    adamw = AdamWConfig(lr=warmup_cosine(lr, steps // 10 + 1, steps))
    plan = session.plan(
        arch, batch=batch, seq=seq, microbatches=microbatches,
        pp_schedule=pp_schedule, comms=comms, adamw=adamw,
        scale_down=scale_down,
        model_kwargs=dict(q_chunk=64, kv_chunk=128, ssd_chunk=32))
    cfg = plan.cfg

    peak = mem_mod.peak_stage_footprint(plan.footprints)
    print(f"memory model: predicted peak {peak.total / mem_mod.GIB:.3f} "
          f"GiB/device vs {plan.budget.describe()} -> fits")
    if plan.comms is not None:
        print(f"comms: grad sync via {plan.comms.schedule} schedule "
              f"(bucket {plan.comms.bucket_bytes >> 20} MiB)")
    if plan.pipeline is not None:
        spec = plan.pipeline
        print(f"pipeline: {spec.n_stages} stages ({spec.schedule}), "
              f"{spec.num_microbatches} microbatches, "
              f"bubble {spec.bubble_fraction():.2f}")

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    resumed = False
    with jax.set_mesh(session.mesh):
        if resume and mgr is not None:
            # restore() walks back past torn/missing snapshots to the
            # newest complete one (and returns None when nothing valid
            # survives — then this run starts fresh rather than crashing)
            state = mgr.restore(shardings=plan.state_shardings())
            if state is not None:
                valid = mgr.valid_steps()
                start_step = valid[-1] if valid else int(
                    jax.device_get(state["opt"]["step"]))
                session.put("train_state", state, kind="train_state")
                resumed = True
                print(f"resumed from step {start_step}")
            else:
                session.init_state(plan, seed=seed)
        else:
            session.init_state(plan, seed=seed)

        source = SyntheticLM(cfg.vocab_size, batch, seq, seed=seed,
                             structured=True)
        if cfg.family == "vlm":
            def add_vision(item):
                import numpy as np
                item = dict(item)
                nv = cfg.n_vision_tokens
                item["tokens"] = item["tokens"][:, :-nv]
                item["labels"][:, :nv] = -1
                item["vision_embeds"] = np.zeros(
                    (batch, nv, cfg.d_model), np.float32)
                return item
            stages = [Stage("vision_stub", add_vision, "host")]
        else:
            stages = []
        # the resilient loop needs deterministic batch order (resume
        # replays the stream to the restored step); 2-thread prefetch
        # reorders, so it drops to a single worker
        pipe = Pipeline(source, stages,
                        n_threads=1 if resilient else 2).start()

        def on_anomaly(step, dt, msg):
            # anomaly -> action (watchdog contract): record the event and
            # cut the early checkpoint the restart story depends on, not
            # just a log line.  Fires with or without --metrics.
            obs.event("watchdog_anomaly", step=step, dt_s=dt, msg=msg)
            if mgr is not None:
                mgr.save(step + 1, session.get("train_state"))
                obs.event("watchdog_checkpoint", step=step + 1)
                print(f"WATCHDOG: early checkpoint at step {step + 1}")

        dog = StepTimeWatchdog(on_anomaly=on_anomaly)
        if resumed:
            # restart hygiene: never judge the resumed run against a
            # step-time distribution learned before the interruption
            dog.reset()
        losses = []
        last_batch = None
        if resilient:
            from repro import faults as faults_mod
            fault_plan = load_fault_plan(faults)
            prev_faults = faults_mod.set_active(fault_plan)
            loop = ResilientStepLoop(session, plan, ckpt=mgr,
                                     ckpt_every=ckpt_every, watchdog=dog,
                                     faults=fault_plan)
            try:
                out = loop.run(pipe, start_step=start_step, steps=steps)
            finally:
                faults_mod.set_active(prev_faults)
                pipe.stop()
            losses = [out["losses"][i] for i in sorted(out["losses"])]
            if out["skipped"]:
                print(f"resilience: skipped steps {out['skipped']} "
                      f"(loss scale {out['loss_scale']:.4g})")
            if fault_plan is not None:
                import json
                print("faults:", json.dumps(fault_plan.summary()))
            if obs.enabled:
                session.publish_metrics()
            return losses
        try:
            for i in range(start_step, steps):
                batch_np = next(pipe)
                t0 = time.perf_counter()
                last_batch = jax.tree.map(jnp.asarray, batch_np)
                metrics_out = session.step(plan, last_batch)
                loss = float(jax.device_get(metrics_out["loss"]))
                dt = time.perf_counter() - t0
                losses.append(loss)
                msg = dog.observe(i, dt)
                if msg:
                    print("WATCHDOG:", msg)
                if (i + 1) % log_every == 0 or i == start_step:
                    print(f"step {i + 1:5d} loss {loss:.4f} "
                          f"({dt * 1e3:.0f} ms)")
                if mgr is not None and (i + 1) % ckpt_every == 0:
                    mgr.save(i + 1, session.get("train_state"))
            if mgr is not None:
                mgr.save(steps, session.get("train_state"), blocking=True)
        finally:
            pipe.stop()

        if obs.enabled:
            session.publish_metrics()
            _measure_peak(session, plan, obs)
            if plan.pipeline is not None and last_batch is not None:
                _measure_bubble(session, plan, last_batch, obs)
            drift = report_mod.session_drift_report(
                plan, {"metrics": session.obs.metrics.summary()})
            print("drift report (predicted vs measured):")
            print(drift.table())
            snap_path = metrics_snapshot or os.path.join(
                os.path.dirname(os.path.abspath(metrics)) or ".",
                "BENCH_step_metrics.json")
            # meta carries the full cell coordinates (batch/seq/scale/...)
            # so the calibration fitter can reconstruct the measured cell
            # from the snapshot alone (calibrate.cell_from_meta).
            from repro.kernels import ops as kops
            obs.snapshot(snap_path, arch=arch, steps=steps,
                         mesh=dict(session.mesh.shape),
                         batch=batch, seq=seq, scale_down=scale_down,
                         microbatches=plan.num_microbatches,
                         pp_schedule=pp_schedule, calibration=calibration,
                         drift=drift.to_dict(),
                         fused_kernels=kops.dispatch_report())
            print(f"metrics: {metrics}  snapshot: {snap_path}")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--scale-down", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--comms", choices=["auto", "off"], default="auto",
                    help="route DP grad sync through repro.comms schedules")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel degree (adds a 'pipe' mesh axis)")
    ap.add_argument("--pp-schedule", choices=["gpipe", "1f1b"],
                    default="gpipe")
    ap.add_argument("--hbm-gib", type=float, default=None,
                    help="per-device HBM budget in GiB for the fail-fast "
                         "memory check (default: platform table)")
    ap.add_argument("--metrics", type=str, default=None, metavar="PATH",
                    help="write a JSONL telemetry stream (spans, counters, "
                         "events) to PATH and a BENCH_step_metrics.json "
                         "snapshot + drift report at exit; default off — "
                         "numerics and output are unchanged without it")
    ap.add_argument("--metrics-snapshot", type=str, default=None,
                    metavar="PATH", help="override the snapshot path "
                    "(default: BENCH_step_metrics.json next to --metrics)")
    ap.add_argument("--calibration", type=str, default=None, metavar="PATH",
                    help="fitted calibration table (python -m repro.fit) to "
                         "plan and predict with; default: hand-set nominal "
                         "constants")
    ap.add_argument("--resilient", action="store_true",
                    help="run the fault-tolerant step loop (rollback/retry "
                         "on non-finite or timed-out steps, watchdog "
                         "escalation to a structured abort); forces "
                         "single-threaded data for deterministic replay")
    ap.add_argument("--faults", type=str, default=None, metavar="JSON",
                    help="fault-injection plan for drills: a JSON file or "
                         "inline JSON list of FaultSpec dicts, e.g. "
                         '\'[{"seam": "train.nonfinite", "step": 3}]\'')
    args = ap.parse_args()
    compile_cache.enable()
    try:
        losses = run(args.arch, steps=args.steps, batch=args.batch,
                     seq=args.seq, scale_down=args.scale_down, lr=args.lr,
                     microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
                     resume=args.resume, seed=args.seed, comms=args.comms,
                     pp=args.pp, pp_schedule=args.pp_schedule,
                     hbm_gib=args.hbm_gib, metrics=args.metrics,
                     metrics_snapshot=args.metrics_snapshot,
                     calibration=args.calibration,
                     resilient=args.resilient, faults=args.faults)
    except PlanMemoryError as e:     # plan validation: clean exit, no trace
        raise SystemExit(str(e))
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
