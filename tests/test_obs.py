"""repro.obs: metric semantics, span tracing, drift report, watchdog wiring.

Pure-host tests for the observability substrate plus two integration
seams: the trace-time comms counters (``sync_tree`` records per-step wire
bytes into the process-wide active Obs) and the watchdog's
anomaly-to-action hook (flag -> ``on_anomaly`` fires, which is what the
train driver uses to cut the early checkpoint).
"""

import json
import os
import threading

import pytest

from repro import obs as obs_mod
from repro.obs import (JsonlSink, MetricRegistry, NullSink, Tracer,
                       read_jsonl, write_snapshot)
from repro.obs import report as report_mod
from repro.train.watchdog import StepTimeWatchdog


# --------------------------------------------------------------------------
# metric registry semantics
# --------------------------------------------------------------------------

def test_counter_and_gauge_semantics():
    reg = MetricRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(41)
    assert c.value == 42
    g = reg.gauge("g")
    g.set(3)
    g.set(1.5)                       # last write wins
    assert g.value == 1.5
    # get-or-create: the same name is the same object
    assert reg.counter("c") is c
    assert reg.gauge("g") is g
    assert reg.histogram("h") is reg.histogram("h")


def test_histogram_buckets_and_percentiles():
    reg = MetricRegistry()
    h = reg.histogram("lat", buckets=[0.001, 0.01, 0.1, 1.0])
    for _ in range(98):
        h.observe(0.005)             # -> 0.01 bucket
    h.observe(0.05)                  # -> 0.1 bucket
    h.observe(5.0)                   # -> overflow bucket
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 0.005 and s["max"] == 5.0
    # p50 interpolates within the (0.001, 0.01] bucket: 50 of its 98
    # samples in, NOT the raw 0.01 bucket edge
    assert s["p50"] == pytest.approx(0.001 + 0.009 * (50 / 98))
    # p99 lands exactly at the top of the (0.01, 0.1] bucket (98 below,
    # its single sample is the 99th)
    assert s["p99"] == pytest.approx(0.1)
    assert h.percentile(1.0) == 5.0  # overflow interpolates up to max
    assert abs(s["mean"] - s["sum"] / 100) < 1e-12


def test_histogram_percentile_does_not_snap_to_bucket_edge():
    # Regression for the drift-report bug: eight ~0.17 s steps reported
    # p50 == 0.2 exactly (the 1-2-5 bucket edge), a +18% phantom drift.
    h = MetricRegistry().histogram("step")
    for v in (0.170, 0.172, 0.175, 0.181, 0.181, 0.187, 0.170):
        h.observe(v)
    p50 = h.percentile(0.5)
    assert p50 != 0.2
    assert 0.17 <= p50 <= 0.19       # clamped into the observed range
    # uniform 1..100 ms: interpolated percentiles track the true ones
    h2 = MetricRegistry().histogram("u")
    for i in range(1, 101):
        h2.observe(i / 1000.0)
    assert h2.percentile(0.5) == pytest.approx(0.0505, rel=0.05)
    assert h2.percentile(0.9) == pytest.approx(0.0905, rel=0.05)


def test_histogram_empty_summary():
    h = MetricRegistry().histogram("empty")
    assert h.summary() == {"count": 0}
    assert h.percentile(0.5) is None


def test_registry_thread_safety_exact_totals():
    reg = MetricRegistry()
    n_threads, per_thread = 8, 2000

    def work():
        for _ in range(per_thread):
            reg.counter("hits").inc()
            reg.histogram("lat").observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hits").value == n_threads * per_thread
    assert reg.histogram("lat").count == n_threads * per_thread


def test_summary_is_json_ready():
    reg = MetricRegistry()
    reg.counter("a").inc(3)
    reg.gauge("b").set(2.5)
    reg.histogram("c").observe(0.1)
    s = json.loads(json.dumps(reg.summary()))
    assert s["counters"]["a"] == 3
    assert s["gauges"]["b"] == 2.5
    assert s["histograms"]["c"]["count"] == 1


# --------------------------------------------------------------------------
# spans + JSONL round-trip
# --------------------------------------------------------------------------

def test_span_nesting_round_trips_through_jsonl(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = JsonlSink(path)
    tracer = Tracer(sink=sink, metrics=MetricRegistry())
    with tracer.span("outer", phase="plan") as outer:
        with tracer.span("inner") as inner:
            pass
    sink.close()
    assert inner.parent == outer.id and outer.parent is None
    events = {e["name"]: e for e in read_jsonl(path)}
    assert events["inner"]["parent"] == events["outer"]["id"]
    assert events["outer"]["parent"] is None
    assert events["outer"]["phase"] == "plan"
    assert all(e["kind"] == "span" and e["dur_s"] >= 0.0
               for e in events.values())


def test_span_attr_cannot_corrupt_event_kind(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = JsonlSink(path)
    tracer = Tracer(sink=sink)
    with tracer.span("plan", kind="train"):
        pass
    sink.close()
    (event,) = read_jsonl(path)
    assert event["kind"] == "span"       # reserved key wins the collision


def test_span_error_recorded_and_histogram_fed(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    reg = MetricRegistry()
    tracer = Tracer(sink=JsonlSink(path), metrics=reg)
    with pytest.raises(ValueError):
        with tracer.span("boom"):
            raise ValueError("x")
    (event,) = read_jsonl(path)
    assert event["error"] == "ValueError"
    assert reg.histogram("span.boom.s").count == 1


# --------------------------------------------------------------------------
# the Obs facade, NULL singleton, snapshots
# --------------------------------------------------------------------------

def test_null_obs_is_inert_and_active_round_trips():
    null = obs_mod.NULL
    assert not null.enabled
    assert null.span("x").__enter__().block(7) == 7
    null.counter("c").inc()
    null.gauge("g").set(1)
    null.histogram("h").observe(1)
    null.event("anything", x=1)
    assert null.counter("c").value == 0

    assert obs_mod.get_active() is obs_mod.NULL
    mine = obs_mod.Obs()
    prev = obs_mod.set_active(mine)
    try:
        assert obs_mod.get_active() is mine
    finally:
        obs_mod.set_active(prev)
    assert obs_mod.get_active() is obs_mod.NULL


def test_obs_snapshot_writes_artifact_and_stream(tmp_path):
    jsonl = str(tmp_path / "m.jsonl")
    snap_path = str(tmp_path / "BENCH_test.json")
    obs = obs_mod.Obs(jsonl=jsonl, name="t")
    obs.counter("wire").inc(128)
    with obs.span("step"):
        pass
    doc = obs.snapshot(snap_path, arch="tiny")
    obs.close()
    assert doc["meta"]["arch"] == "tiny"
    on_disk = json.load(open(snap_path))
    assert on_disk["metrics"]["counters"]["wire"] == 128
    assert on_disk["metrics"]["histograms"]["span.step.s"]["count"] == 1
    kinds = [e["kind"] for e in read_jsonl(jsonl)]
    assert kinds.count("metrics") == 1 and "span" in kinds


def test_null_sink_and_atomic_snapshot(tmp_path):
    NullSink().write({"kind": "x"})          # no-op, no file
    p = str(tmp_path / "sub" / "BENCH_x.json")
    write_snapshot(p, {"a": 1})
    assert json.load(open(p)) == {"a": 1}
    assert not os.path.exists(p + ".tmp")


# --------------------------------------------------------------------------
# drift report
# --------------------------------------------------------------------------

def test_drift_tolerance_flags_only_beyond():
    rep = report_mod.drift_report(
        predicted={"bubble_fraction": 0.20, "peak_bytes": 1e9,
                   "only_predicted": 1.0},
        measured={"bubble_fraction": 0.22, "peak_bytes": 2e9})
    rows = {r.name: r for r in rep.rows}
    assert set(rows) == {"bubble_fraction", "peak_bytes"}  # join drops gaps
    assert not rows["bubble_fraction"].flagged            # +10% < 25% tol
    assert rows["peak_bytes"].flagged                     # +100% > 20% tol
    assert rep.flagged == [rows["peak_bytes"]]
    table = rep.table()
    assert "DRIFT" in table and "ok" in table
    d = rep.to_dict()
    assert d["n_flagged"] == 1 and len(d["rows"]) == 2


def test_default_tolerances_are_calibrated_tight():
    # the step_time_s 10.0 (1000%) hack must stay dead: tolerances assume
    # the calibrate loop ran and are sized to run-to-run noise
    assert report_mod.DEFAULT_TOLERANCES["step_time_s"] <= 0.5
    assert report_mod.DEFAULT_TOLERANCES["bubble_fraction"] <= 0.25
    assert report_mod.DEFAULT_TOLERANCES["peak_bytes"] <= 0.2


def test_drift_report_sign_and_custom_tolerance():
    rep = report_mod.drift_report({"m": 10.0}, {"m": 7.0},
                                  tolerances={"m": 0.2})
    (row,) = rep.rows
    assert row.drift == pytest.approx(-0.3)
    assert row.flagged                       # |-30%| > 20%


def test_measured_bubble_fraction_recovers_cost_model():
    # synthetic pipeline: t(M) = t_mb * (M + S - 1) -> the slope estimator
    # must recover bubble(M) = (S-1)/(M+S-1) exactly
    s, t_mb = 4, 0.01
    times = {m: t_mb * (m + s - 1) for m in (2, 4, 8)}
    got = report_mod.measured_bubble_fraction(times)
    for m in times:
        assert got[m] == pytest.approx((s - 1) / (m + s - 1))
    with pytest.raises(ValueError):
        report_mod.measured_bubble_fraction({4: 0.1})


def test_measured_from_summary_reads_the_contract_names():
    obs = obs_mod.Obs()
    obs.histogram(report_mod.MEASURED_STEP_HISTOGRAM).observe(0.5)
    obs.gauge(report_mod.MEASURED_BUBBLE_GAUGE).set(0.25)
    obs.gauge(report_mod.MEASURED_PEAK_GAUGE).set(1e9)
    snap = obs.snapshot()
    meas = report_mod.measured_from_summary(snap)   # snapshot wrapper form
    assert set(meas) == {"step_time_s", "bubble_fraction", "peak_bytes"}
    assert meas["bubble_fraction"] == 0.25 and meas["peak_bytes"] == 1e9


# --------------------------------------------------------------------------
# watchdog: anomaly -> action
# --------------------------------------------------------------------------

def test_watchdog_warmup_never_flags():
    fired = []
    dog = StepTimeWatchdog(on_anomaly=lambda *a: fired.append(a))
    # wildly varying warmup (compile steps) must not flag
    for i, dt in enumerate([5.0, 0.1, 3.0, 0.1, 0.1]):
        assert dog.observe(i, dt) is None
    assert not dog.anomalies and not fired


def test_watchdog_steady_state_never_flags():
    dog = StepTimeWatchdog()
    for i in range(200):
        assert dog.observe(i, 0.1 + 1e-4 * (i % 3)) is None
    assert not dog.anomalies


def test_watchdog_flags_10x_step_and_fires_hook_once():
    fired = []
    dog = StepTimeWatchdog(on_anomaly=lambda s, dt, msg:
                           fired.append((s, dt, msg)))
    for i in range(50):
        dog.observe(i, 0.1 + 1e-3 * (i % 5))
    msg = dog.observe(50, 1.0)               # injected 10x straggler
    assert msg is not None and "straggler" in msg
    assert dog.anomalies == [50]
    assert len(fired) == 1
    step, dt, hook_msg = fired[0]
    assert step == 50 and dt == 1.0 and hook_msg == msg


# --------------------------------------------------------------------------
# trace-time comms counters (sync_tree -> active Obs)
# --------------------------------------------------------------------------

def test_sync_tree_records_per_step_wire_bytes():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comms import CommsPlan, sync_tree
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    plan = CommsPlan(schedule="psum")
    grads = {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))}
    n_bytes = 4 * (8 * 4 + 4)

    obs = obs_mod.Obs(name="t")
    prev = obs_mod.set_active(obs)
    try:
        fn = jax.jit(jax.shard_map(
            lambda g: sync_tree(g, plan, mesh, ("data",)),
            check_vma=False, mesh=mesh,
            in_specs=(P(),), out_specs=P()))
        fn(grads)          # trace 1: counters record once per compile
        fn(grads)          # cache hit: no re-trace, no double count
    finally:
        obs_mod.set_active(prev)
    assert obs.counter("comms.wire_bytes").value == n_bytes
    assert obs.counter("comms.psum.wire_bytes").value == n_bytes
    assert obs.counter("comms.psum.buckets").value >= 1
    # metrics off: the same trace records nothing through NULL
    assert obs_mod.NULL.counter("comms.wire_bytes").value == 0


# --------------------------------------------------------------------------
# Session integration: spans stream, numerics untouched
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_session_obs_streams_spans_and_keeps_losses_bit_identical(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import Session
    from repro.launch.mesh import make_mesh
    from repro.train import AdamWConfig

    def losses(obs):
        prev = obs_mod.set_active(obs if obs is not None else obs_mod.NULL)
        try:
            sess = Session(mesh=make_mesh((1, 1), ("data", "model")),
                           obs=obs)
            plan = sess.plan("qwen2-0.5b", batch=4, seq=16,
                             adamw=AdamWConfig(lr=1e-3), scale_down=64,
                             model_kwargs=dict(q_chunk=8, kv_chunk=8))
            rng = np.random.RandomState(0)
            out = []
            with jax.set_mesh(sess.mesh):
                sess.init_state(plan, seed=0)
                for _ in range(3):
                    toks = rng.randint(0, plan.cfg.vocab_size,
                                       (4, 17)).astype(np.int32)
                    batch = {"tokens": jnp.asarray(toks[:, :-1]),
                             "labels": jnp.asarray(toks[:, 1:])}
                    m = sess.step(plan, batch)
                    out.append(float(jax.device_get(m["loss"])))
            return out
        finally:
            obs_mod.set_active(prev)

    off = losses(None)
    jsonl = str(tmp_path / "m.jsonl")
    obs = obs_mod.Obs(jsonl=jsonl)
    on = losses(obs)
    obs.close()
    assert on == off                       # telemetry must not touch math

    events = read_jsonl(jsonl)
    spans = [e["name"] for e in events if e["kind"] == "span"]
    assert "plan" in spans and "build_step" in spans
    # compile-bearing steps are labeled warmup (the opcache-miss first
    # step, plus any jit re-specialization for the updated state's
    # shardings); only steady-state steps feed the histogram the drift
    # report reads, and at least the last step must be steady
    step_spans = [s for s in spans if s in ("step", "step_warmup")]
    assert len(step_spans) == 3
    assert step_spans[0] == "step_warmup"
    assert step_spans[-1] == "step"
    assert any(e["kind"] == "plan_resolved" for e in events)
    # the step spans blocked on device outputs and fed the histograms
    assert obs.histogram("span.step_warmup.s").count == \
        step_spans.count("step_warmup")
    assert obs.histogram("span.step.s").count == step_spans.count("step")
    # opcache/state gauges were published on the instrumented path
    assert obs.gauge("state.resident_bytes").value > 0


# --------------------------------------------------------------------------
# spans on the profiler clock; the serve loop's phase spans
# --------------------------------------------------------------------------

class _Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation`` recording
    (name, enter/exit) in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Ann:
            def __enter__(self):
                log.append((name, "enter"))

            def __exit__(self, *exc):
                log.append((name, "exit"))
        return Ann()


def test_spans_hold_a_profiler_annotation_named_without_attrs(monkeypatch):
    from repro.obs import trace as trace_mod
    anns = _Annotations()
    monkeypatch.setattr(trace_mod, "TraceAnnotation", anns)
    obs = obs_mod.Obs()
    with obs.span("outer", phase="plan"):
        with obs_mod.NULL.span("inner", x=1):
            pass
    assert anns.log == [("repro.outer", "enter"), ("repro.inner", "enter"),
                        ("repro.inner", "exit"), ("repro.outer", "exit")]
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    assert anns.log[-1] == ("repro.boom", "exit")


def test_null_span_is_annotation_only(monkeypatch):
    import jax

    def no_sync(*a, **k):
        raise AssertionError("a NULL span synced")

    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    sp = obs_mod.NULL.span("serve.tick", slot=3)
    assert isinstance(sp, obs_mod.AnnotationSpan)
    with sp:
        assert sp.block(7) == 7
    assert sp.seconds == 0.0 and sp.id is None and sp.parent is None
    assert obs_mod.NULL.metrics.summary() == \
        obs_mod.MetricRegistry().summary()


SERVE_TINY = dict(name="obs-serve-tiny", family="dense", n_layers=2,
                  d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                  d_ff=128, vocab_size=64)


@pytest.fixture(scope="module")
def tiny_serve():
    """run(obs) -> (outputs by rid, ticks) of three ragged requests
    through a two-slot ContinuousEngine; the engines share one compiled
    step set."""
    import jax
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.core.opcache import OpCache
    from repro.core.planner import plan_for
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.serve import ContinuousEngine, Request

    cfg = ModelConfig(**SERVE_TINY)
    mesh = make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        model = Model(cfg, mesh, plan_for(cfg, mesh), q_chunk=16,
                      kv_chunk=16)
        params = jax.device_put(model.init(jax.random.PRNGKey(3)),
                                model.param_shardings())
    cache = OpCache("test-obs")

    def run(obs):
        with jax.set_mesh(mesh):
            eng = ContinuousEngine(model, params, batch_slots=2,
                                   max_seq=32, page_size=8, prefill_chunk=4,
                                   opcache=cache, obs=obs)
            rng = np.random.default_rng(0)
            for r in range(3):
                eng.submit(Request(
                    rid=r, prompt=rng.integers(0, 64, 3 + 2 * r,
                                               dtype=np.int32),
                    max_new_tokens=4))
            ticks = 0
            while eng.queue or any(r is not None for r in eng.active):
                eng.step()
                ticks += 1
        return {r.rid: list(r.out) for r in eng.finished}, ticks

    run(None)                                   # compile
    return run


def test_serve_obs_on_makes_the_same_tokens_without_syncing(
        tiny_serve, monkeypatch):
    import jax

    off, ticks = tiny_serve(None)

    def no_sync(*a, **k):
        raise AssertionError("telemetry synced the serve loop")

    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    obs = obs_mod.Obs()
    on, ticks_on = tiny_serve(obs)
    assert on == off and ticks_on == ticks and len(on) == 3
    h = obs.metrics.summary()["histograms"]
    assert h["span.serve.tick.s"]["count"] == ticks
    assert h["span.serve.readback.s"]["count"] == \
        h["span.serve.sample.s"]["count"]
    assert h["serve.ttft_s"]["count"] == 3
    assert "serve.prefill_s" not in h and "serve.decode_s" not in h


def test_profiler_capture_holds_one_tick_span_per_tick(tiny_serve,
                                                       tmp_path):
    """Telemetry off: the phases still reach a CPU profiler trace."""
    import jax

    from bench import program_trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, ticks = tiny_serve(None)
    finally:
        jax.profiler.stop_trace()
    p = program_trace.reduce(str(tmp_path))
    n_tick, tick_s, _ = p.program["repro.serve.tick"]
    n_sample, sample_s, _ = p.program["repro.serve.sample"]
    n_read, read_s, _ = p.program["repro.serve.readback"]
    assert n_tick == ticks and n_read == n_sample
    assert read_s <= sample_s <= tick_s
    for phase in ("admit", "extend"):
        assert p.count("repro.serve." + phase) == ticks
    # three prompts of 3, 5 and 7 tokens in chunks of 4
    assert p.count("repro.serve.prefill") == 1 + 2 + 2
    # every span sits inside a tick: the ticks' self time is what is left
    inner = sum(t for n, (_, _, t) in p.program.items()
                if n != "repro.serve.tick")
    assert inner + p.program["repro.serve.tick"][2] == \
        pytest.approx(tick_s, rel=1e-6)
