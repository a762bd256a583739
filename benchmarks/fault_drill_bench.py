"""Fault-injection recovery drill (run.py section ``fault_drill``).

The robustness acceptance test for §2 requirement (e): every fault the
harness can inject is injected ONCE into a small end-to-end run on 8
fake host devices, and the drill FAILS (nonzero exit) unless every one
of them is *recovered* — detected, handled by the matching policy, and
the run completed with the right trajectory:

Train drill (``repro.train.resilience`` over a real ``Session``):

- ``comms.sync_tree``   timeout raised inside the gradient sync at trace
                        time -> bounded-backoff retry re-traces cleanly;
- ``train.nonfinite``   committed update poisoned to NaN -> rollback to
                        the host snapshot + retry the SAME batch, so the
                        pre-restart trajectory is BIT-IDENTICAL to the
                        no-fault oracle;
- ``comms.timeout``     step-boundary timeout -> same retry path;
- ``train.straggler``   two injected delays -> watchdog anomalies ->
                        escalation: early checkpoint + structured
                        StepAbort -> the elastic driver re-plans on a
                        SMALLER mesh (8 -> 4 devices) and resumes (the
                        DP reduction order changes, so post-restart
                        losses match the oracle to rtol, not bitwise);
- ``checkpoint.torn``   kill-mid-write leaves a torn snapshot with
                        LATEST pointing at it -> restore walks back to
                        the newest complete snapshot and replays.

Serve drill (``repro.faults.arm_engine`` on a ContinuousEngine):

- ``serve.pool_storm``  KV pages stolen mid-run -> decode growth hits
                        PoolExhausted -> preempt/requeue -> admitted
                        requests still finish with outputs bit-identical
                        to a storm-free oracle run;
- deadline TTLs         expired queued work is shed with a structured
                        DeadlineExceeded (never silently dropped);
- preempt cycle bound   a request that circulates past the restart cap
                        converts into a permanent AdmissionRefusal
                        (``reason="preempt_cycle"``).

Commits ``experiments/fault_drill.json`` with per-fault injected /
recovered counts and recovery latencies.  CSV columns: name,
us_per_call, derived.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.bench_util import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "experiments", "fault_drill.json")

# train cell: tiny dense model, pure-DP so comms routes through the
# repro.comms schedules (the sync_tree seam must actually be on the path)
B, SEQ, STEPS, CKPT_EVERY = 8, 16, 16, 3
#: elastic re-plan: attempt 0 runs DP=8, every restart runs DP=4
FULL_DP, ELASTIC_DP = 8, 4
#: post-restart losses come from a different reduction order
ELASTIC_RTOL = 1e-3

# serve cell: 3 slots over 12 usable pages of 8 tokens; each request
# wants 4 pages end-to-end, so 3 actives fill the pool exactly and the
# storm's stolen pages force preemption
SLOTS, MAX_SEQ, PAGE, NUM_PAGES = 3, 96, 8, 13
PROMPT, MAX_NEW, OFFERED = 16, 16, 5
STORM_TICK, STORM_PAGES, STORM_TICKS = 4, 6, 6


def _tiny_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="drill-tiny", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                       d_ff=64, vocab_size=128)


# ---------------------------------------------------------------------------
# train drill
# ---------------------------------------------------------------------------

def _session_factory(cfg, obs):
    import jax  # noqa: F401

    from repro.api import Session
    from repro.launch.mesh import make_mesh

    def factory(attempt: int):
        dp = FULL_DP if attempt == 0 else ELASTIC_DP
        mesh = make_mesh((dp, 1), ("data", "model"))
        sess = Session(mesh=mesh, obs=obs)
        plan = sess.plan(cfg, batch=B, seq=SEQ, comms="auto",
                         model_kwargs=dict(q_chunk=16, kv_chunk=16))
        return sess, plan

    return factory


def _data_factory(cfg):
    from repro.data import SyntheticLM

    def factory():
        return SyntheticLM(cfg.vocab_size, B, SEQ, seed=0, structured=True)

    return factory


def _train_drill() -> dict:
    import jax

    from repro import obs as obs_mod
    from repro.checkpoint import CheckpointManager
    from repro.faults import FaultPlan, FaultSpec, set_active
    from repro.train import ElasticRunner, ResilientStepLoop, \
        StepTimeWatchdog
    from repro.train.resilience import ResilienceConfig

    cfg = _tiny_cfg()

    # oracle: the full-mesh run with no faults and no checkpoints
    sess, plan = _session_factory(cfg, obs_mod.NULL)(0)
    with jax.set_mesh(sess.mesh):
        sess.init_state(plan, seed=0)
        oracle = ResilientStepLoop(sess, plan).run(
            iter(_data_factory(cfg)()), start_step=0, steps=STEPS)

    obs = obs_mod.Obs(name="fault_drill/train")
    plan_specs = [
        # step=None: fires the first time sync_tree is traced (step 0)
        FaultSpec("comms.sync_tree"),
        FaultSpec("train.nonfinite", step=2),
        FaultSpec("comms.timeout", step=4),
        # escalating delays: the second must out-z the EMA the first fed
        FaultSpec("train.straggler", step=7, magnitude=0.25),
        FaultSpec("train.straggler", step=8, magnitude=1.0),
        # ckpt_every=3 labels 3,6,9,...; the escalation checkpoint lands
        # on label 9, then the torn write kills the resumed attempt at 12
        FaultSpec("checkpoint.torn", step=12),
    ]
    faults = FaultPlan(plan_specs, seed=0)
    rcfg = ResilienceConfig(anomaly_window=8, anomaly_limit=2,
                            backoff_base_s=0.05)

    import tempfile
    t0 = time.perf_counter()
    prev = set_active(faults)      # arms the trace-time sync_tree seam
    try:
        with tempfile.TemporaryDirectory() as ckdir:
            runner = ElasticRunner(
                _session_factory(cfg, obs), _data_factory(cfg),
                ckpt=CheckpointManager(ckdir), steps=STEPS,
                ckpt_every=CKPT_EVERY, config=rcfg, faults=faults,
                seed=0,
                # compile-bearing steps are not fed to the dog, and the
                # retries at steps 0/2 each recompile — a short warmup
                # keeps the EMA primed before the step-7/8 stragglers
                watchdog_factory=lambda: StepTimeWatchdog(warmup_steps=3))
            out = runner.run()
    finally:
        set_active(prev)
    wall = time.perf_counter() - t0

    # -- verdicts ----------------------------------------------------------
    restarts = out["restarts"]
    by_reason = {r["reason"]: r for r in restarts}
    esc = by_reason.get("watchdog_escalation")
    torn = by_reason.get("checkpoint.torn")
    first_restored = restarts[0]["restored_step"] if restarts else STEPS

    errs_pre = [abs(out["losses"][i] - oracle["losses"][i])
                for i in range(min(first_restored, STEPS))]
    rel_elastic = [abs(out["losses"][i] - oracle["losses"][i])
                   / abs(oracle["losses"][i])
                   for i in range(first_restored, STEPS)]

    counters = {k: obs.counter(k).value for k in
                ("resil.retries", "resil.nonfinite", "resil.rollbacks",
                 "resil.anomalies", "resil.aborts", "resil.skipped_steps",
                 "resil.torn_checkpoints")}

    faults_out = {
        "comms.sync_tree": {
            "injected": faults.injected("comms.sync_tree"),
            "recovered": int(counters["resil.retries"] >= 2),
            "recovery_latency_s": rcfg.backoff_base_s,
            "action": "retrace after backoff"},
        "train.nonfinite": {
            "injected": faults.injected("train.nonfinite"),
            "recovered": int(counters["resil.rollbacks"] >= 1
                             and (not errs_pre or max(errs_pre) == 0.0)),
            "recovery_latency_s": None,   # one extra step, no sleep
            "action": "rollback + retry same batch (bitwise)"},
        "comms.timeout": {
            "injected": faults.injected("comms.timeout"),
            "recovered": int(counters["resil.retries"] >= 2),
            "recovery_latency_s": rcfg.backoff_base_s,
            "action": "retry after backoff"},
        "train.straggler": {
            "injected": faults.injected("train.straggler"),
            # the burst recovers as a unit: one escalation covers every
            # delay that fed it
            "recovered": faults.injected("train.straggler")
            if esc is not None and esc["steps_lost"] == 0 else 0,
            "recovery_latency_s": esc["recovery_s"] if esc else None,
            "action": "escalate -> early ckpt -> elastic restart "
                      f"(DP {FULL_DP} -> {ELASTIC_DP})"},
        "checkpoint.torn": {
            "injected": faults.injected("checkpoint.torn"),
            "recovered": int(torn is not None
                             and torn["restored_step"] < 12),
            "recovery_latency_s": torn["recovery_s"] if torn else None,
            "action": "walk back to newest complete snapshot"},
    }
    unrecovered = sum(f["injected"] - f["recovered"]
                      for f in faults_out.values()) + faults.pending()

    return {
        "steps": STEPS, "attempts": out["attempts"],
        "restarts": restarts, "counters": counters,
        "faults": faults_out, "fault_summary": faults.summary(),
        "oracle_final_loss": oracle["losses"][STEPS - 1],
        "drill_final_loss": out["final_loss"],
        "pre_restart_max_abs_err": max(errs_pre) if errs_pre else None,
        "elastic_max_rel_err": max(rel_elastic) if rel_elastic else None,
        "elastic_rtol": ELASTIC_RTOL,
        "skipped_steps": out["skipped"],
        "wall_s": wall,
        "unrecovered": unrecovered
        + int(bool(errs_pre) and max(errs_pre) > 0.0)
        + int(bool(rel_elastic) and max(rel_elastic) > ELASTIC_RTOL),
    }


# ---------------------------------------------------------------------------
# serve drill
# ---------------------------------------------------------------------------

def _serve_engine(model, params, opcache, obs):
    from repro.serve import ContinuousEngine
    return ContinuousEngine(model, params, batch_slots=SLOTS,
                            max_seq=MAX_SEQ, page_size=PAGE,
                            num_pages=NUM_PAGES, prefill_chunk=PAGE,
                            opcache=opcache, obs=obs)


def _requests(with_deadlines: bool):
    from repro.serve import Request
    rng = np.random.default_rng(7)
    reqs = [Request(rid=r,
                    prompt=rng.integers(0, 128, PROMPT, dtype=np.int32),
                    max_new_tokens=MAX_NEW) for r in range(OFFERED)]
    if with_deadlines:
        # TTL already elapsed by the first tick: must be SHED with a
        # structured DeadlineExceeded, never silently dropped
        reqs += [Request(rid=100 + i,
                         prompt=rng.integers(0, 128, PROMPT,
                                             dtype=np.int32),
                         max_new_tokens=MAX_NEW, deadline_s=1e-9)
                 for i in range(2)]
    return reqs


def _drain(eng, max_ticks=3000):
    ticks = 0
    while (eng.sched.queue or any(r is not None for r in eng.active)) \
            and ticks < max_ticks:
        eng.step()
        ticks += 1
    return ticks


def _preempt_cycle_drill(cfg) -> dict:
    """Deterministic cycle-bound check at the scheduler layer: a request
    preempted past ``max_preempt_restarts`` converts into the permanent
    structured refusal instead of circulating forever."""
    from repro.serve import BlockManager, Request, Scheduler
    blocks = BlockManager(cfg, num_pages=NUM_PAGES, page_size=PAGE,
                          max_seq=MAX_SEQ)
    sched = Scheduler(blocks, max_preempt_restarts=2)
    req = Request(rid=999, prompt=np.zeros(PROMPT, np.int32),
                  max_new_tokens=MAX_NEW)
    sched.submit(req)
    sched.queue.remove(req)            # "admit" it
    verdicts = [sched.requeue_preempted(req) for _ in range(3)]
    if verdicts[2] is not None:
        sched.queue.clear()
    return {"preempts_before_refusal": 2,
            "refusal": verdicts[2].to_dict() if verdicts[2] else None,
            "converted": verdicts[:2] == [None, None]
            and verdicts[2] is not None
            and verdicts[2].reason == "preempt_cycle"}


def _serve_drill() -> dict:
    import jax

    from repro import obs as obs_mod
    from repro.core.opcache import OpCache
    from repro.core.planner import plan_for
    from repro.faults import FaultPlan, FaultSpec, arm_engine
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    cfg = _tiny_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    opcache = OpCache("fault_drill")
    with jax.set_mesh(mesh):
        model = Model(cfg, mesh, plan_for(cfg, mesh), q_chunk=16,
                      kv_chunk=16)
        params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                                model.param_shardings())

        # oracle: same offered load, no storm, no deadline pressure
        eng0 = _serve_engine(model, params, opcache, obs_mod.NULL)
        for r in _requests(with_deadlines=False):
            eng0.submit(r)
        _drain(eng0)
        oracle_out = {r.rid: list(r.out) for r in eng0.finished}

        # drill: pool storm + already-expired TTLs
        obs = obs_mod.Obs(name="fault_drill/serve")
        eng = _serve_engine(model, params, opcache, obs)
        faults = FaultPlan([FaultSpec("serve.pool_storm", step=STORM_TICK,
                                      magnitude=STORM_PAGES,
                                      duration=STORM_TICKS)])
        arm_engine(faults, eng)
        t0 = time.perf_counter()
        for r in _requests(with_deadlines=True):
            eng.submit(r)
        ticks = _drain(eng)
        wall = time.perf_counter() - t0

    drill_out = {r.rid: list(r.out) for r in eng.finished}
    identical = all(drill_out.get(rid) == oracle_out[rid]
                    for rid in oracle_out)
    shed = [r.refusal.to_dict() for r in eng.shed]
    preempts = obs.counter("serve.preemptions").value
    cycle = _preempt_cycle_drill(cfg)

    faults_out = {
        "serve.pool_storm": {
            "injected": faults.injected("serve.pool_storm"),
            "recovered": int(faults.injected("serve.pool_storm") == 1
                             and len(drill_out) == OFFERED and identical),
            "recovery_latency_s": None,
            "action": f"preempt/requeue under pressure ({preempts} "
                      "preemptions), outputs bit-identical"},
        "serve.deadline": {
            "injected": 2,
            "recovered": len([s for s in shed
                              if s["reason"] == "deadline"]),
            "recovery_latency_s": max((s["waited_s"] for s in shed),
                                      default=None),
            "action": "shed queued work with structured "
                      "DeadlineExceeded"},
        "serve.preempt_cycle": {
            "injected": 1,
            "recovered": int(cycle["converted"]),
            "recovery_latency_s": None,
            "action": "convert to permanent AdmissionRefusal "
                      "(preempt_cycle) after the restart cap"},
    }
    unrecovered = sum(f["injected"] - f["recovered"]
                      for f in faults_out.values())
    return {
        "offered": OFFERED, "completed": len(drill_out), "ticks": ticks,
        "faults": faults_out, "fault_summary": faults.summary(),
        "preemptions": preempts,
        "deadline_shed": shed,
        "preempt_cycle": cycle,
        "outputs_bitwise_identical": identical,
        "wall_s": wall,
        "unrecovered": unrecovered,
    }


# ---------------------------------------------------------------------------

def main():
    t0 = time.perf_counter()
    train = _train_drill()
    serve = _serve_drill()
    total_unrecovered = train["unrecovered"] + serve["unrecovered"]

    emit("fault_drill_train", 1e6 * train["wall_s"] / STEPS,
         f"attempts={train['attempts']};"
         f"restarts={len(train['restarts'])};"
         f"pre_err={train['pre_restart_max_abs_err']};"
         f"elastic_rel={train['elastic_max_rel_err']:.2e};"
         f"unrecovered={train['unrecovered']}")
    emit("fault_drill_serve", 1e6 * serve["wall_s"] / max(1, serve["ticks"]),
         f"completed={serve['completed']}/{serve['offered']};"
         f"preempt={serve['preemptions']};"
         f"shed={len(serve['deadline_shed'])};"
         f"bitwise={serve['outputs_bitwise_identical']};"
         f"unrecovered={serve['unrecovered']}")

    doc = {"meta": {"steps": STEPS, "batch": B, "seq": SEQ,
                    "ckpt_every": CKPT_EVERY, "full_dp": FULL_DP,
                    "elastic_dp": ELASTIC_DP, "arch": "drill-tiny",
                    "serve": {"slots": SLOTS, "page_size": PAGE,
                              "num_pages": NUM_PAGES, "prompt": PROMPT,
                              "max_new": MAX_NEW, "offered": OFFERED},
                    "wall_s": time.perf_counter() - t0,
                    "t_wall": time.time()},
           "train": train, "serve": serve,
           "unrecovered_total": total_unrecovered}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, OUT)
    emit("fault_drill_artifact", 0.0, OUT)

    if total_unrecovered:
        raise SystemExit(
            f"fault_drill: {total_unrecovered} injected faults were NOT "
            f"recovered (see {OUT})")


if __name__ == "__main__":
    main()
