"""Continuous-batching saturation sweep (run.py section ``serve_saturation``).

Drives :class:`repro.serve.ContinuousEngine` on a tiny dense model at
three offered-load points against a deliberately undersized page pool, so
every governance path fires at least once in the committed artifact:

- **low** load fits the pool — no preemptions, pool utilization well
  under 1;
- **mid/high** load oversubscribes it — lazy decode growth collides,
  the scheduler preempts-and-requeues, and completed throughput
  saturates while queue wait grows;
- every point also offers one impossible request (footprint beyond pool
  capacity), which must be refused up front with a structured
  :class:`~repro.serve.AdmissionRefusal` — never admitted then OOMed.

Per point we record requests/s, TTFT p50, per-token latency p50/p99,
peak pool utilization, preemption count, and the structured refusals,
then commit the sweep to ``experiments/serve_saturation.json``.  The
section FAILS if any tick observes more pages in use than the pool
holds (an "OOM admission") or if any refusal is missing its reason.

CSV columns: name, us_per_call, derived.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.bench_util import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "experiments", "serve_saturation.json")

#: bench cell: 4 decode slots over a pool that holds 10 usable pages of
#: 8 tokens — each request needs 4 pages end-to-end (16-token prompt +
#: 16 new), so 4 concurrent sequences want 16 pages > 10 and the lazy
#: growth path must preempt under load.
BATCH_SLOTS = 4
MAX_SEQ = 96
PAGE_SIZE = 8
NUM_PAGES = 11
PREFILL_CHUNK = 8
PROMPT_LEN = 16
MAX_NEW = 16
LOADS = (2, 6, 12)          # offered requests per point: under/at/over pool


def _tiny_model():
    import jax

    from repro.configs.base import ModelConfig
    from repro.core.planner import plan_for
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    cfg = ModelConfig(name="serve-bench-tiny", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=64)
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = plan_for(cfg, mesh)
    model = Model(cfg, mesh, plan, q_chunk=16, kv_chunk=16)
    params = model.init(jax.random.PRNGKey(0))
    params = jax.device_put(params, model.param_shardings())
    return mesh, model, params


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def _run_point(model, params, opcache, offered: int) -> dict:
    from repro import obs as obs_mod
    from repro.serve import ContinuousEngine, Request

    obs = obs_mod.Obs(name=f"serve_saturation/load{offered}")
    eng = ContinuousEngine(model, params, batch_slots=BATCH_SLOTS,
                           max_seq=MAX_SEQ, page_size=PAGE_SIZE,
                           num_pages=NUM_PAGES,
                           prefill_chunk=PREFILL_CHUNK,
                           opcache=opcache, obs=obs)
    rng = np.random.default_rng(offered)
    for rid in range(offered):
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(0, model.cfg.vocab_size, PROMPT_LEN,
                                dtype=np.int32),
            max_new_tokens=MAX_NEW))
    # two impossible requests — one per refusal reason: a footprint the
    # pool can never hold (pool_capacity) and a sequence past the
    # position window (seq_window).  Both must be structured up-front
    # refusals, never admissions that OOM later.
    eng.submit(Request(rid=10_000 + offered,
                       prompt=np.zeros(MAX_SEQ - MAX_NEW, dtype=np.int32),
                       max_new_tokens=MAX_NEW))
    eng.submit(Request(rid=20_000 + offered,
                       prompt=np.zeros(MAX_SEQ, dtype=np.int32),
                       max_new_tokens=MAX_NEW))

    t0 = time.perf_counter()
    peak_used, oom_ticks, ticks = 0, 0, 0
    while (eng.queue or any(r is not None for r in eng.active)) \
            and ticks < 10_000:
        eng.step()
        used = eng.blocks.used_pages
        peak_used = max(peak_used, used)
        if used > eng.blocks.capacity_pages:
            oom_ticks += 1
        ticks += 1
    wall = time.perf_counter() - t0

    fin = [r for r in eng.finished if r.refusal is None]
    tokens = sum(len(r.out) for r in fin)
    ttft = [r.first_token_t - r.submit_t for r in fin
            if r.first_token_t is not None]
    per_tok = [(r.finish_t - r.first_token_t) / max(1, len(r.out) - 1)
               for r in fin if r.first_token_t is not None and len(r.out) > 1]
    refusals = [r.to_dict() for r in
                (req.refusal for req in eng.refused) if r is not None]
    return {
        "offered": offered,
        "completed": len(fin),
        "tokens": tokens,
        "wall_s": wall,
        "requests_per_s": len(fin) / wall if wall else 0.0,
        "tok_per_s": tokens / wall if wall else 0.0,
        "ttft_p50_s": _percentile(ttft, 50),
        "per_token_p50_s": _percentile(per_tok, 50),
        "per_token_p99_s": _percentile(per_tok, 99),
        "pool_util_peak": peak_used / eng.blocks.capacity_pages,
        "preemptions": obs.counter("serve.preemptions").value,
        "oom_admissions": oom_ticks,
        "refusals": refusals,
    }


def main():
    import jax

    from repro.core.opcache import OpCache

    mesh, model, params = _tiny_model()
    opcache = OpCache("serve_saturation")   # compile once across load points
    points = []
    with jax.set_mesh(mesh):
        _run_point(model, params, opcache, 1)   # warmup: pay compiles once
        for offered in LOADS:
            pt = _run_point(model, params, opcache, offered)
            points.append(pt)
            emit(f"serve_saturation_load{offered}",
                 1e6 * pt["wall_s"] / max(1, pt["tokens"]),
                 f"req/s={pt['requests_per_s']:.2f};"
                 f"ttft_p50={pt['ttft_p50_s'] * 1e3:.1f}ms;"
                 f"tok_p99={pt['per_token_p99_s'] * 1e3:.1f}ms;"
                 f"util={pt['pool_util_peak']:.2f};"
                 f"preempt={pt['preemptions']};"
                 f"refused={len(pt['refusals'])}")

    bad = [p["offered"] for p in points if p["oom_admissions"]]
    if bad:
        raise SystemExit(f"serve_saturation: pool over-commit at load {bad}")
    missing = [p["offered"] for p in points
               if {r.get("reason") for r in p["refusals"]}
               != {"pool_capacity", "seq_window"}]
    if missing:
        raise SystemExit("serve_saturation: impossible requests were not "
                         f"structurally refused at load {missing}")
    incomplete = [p["offered"] for p in points if p["completed"] != p["offered"]]
    if incomplete:
        raise SystemExit(f"serve_saturation: dropped requests at load "
                         f"{incomplete}")

    doc = {"meta": {"batch_slots": BATCH_SLOTS, "max_seq": MAX_SEQ,
                    "page_size": PAGE_SIZE, "num_pages": NUM_PAGES,
                    "prefill_chunk": PREFILL_CHUNK,
                    "prompt_len": PROMPT_LEN, "max_new_tokens": MAX_NEW,
                    "arch": "serve-bench-tiny", "t_wall": time.time()},
           "points": points}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, OUT)
    emit("serve_saturation_artifact", 0.0, OUT)


if __name__ == "__main__":
    main()
