"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

A thin CLI over :class:`repro.api.Session`: ``Session.plan`` (decode
kind) -> ``Session.serve`` (batched engine on the session's persistent
params + KV cache, jitted steps in the session's compiled-artifact
cache), feeds synthetic prompts, reports tokens/sec — the inference
counterpart of launch/train.py.

``--scheduler continuous`` runs the continuous-batching engine (paged KV
block pool + budget-governed admission, chunked prefill, preempt-and-
requeue); ``--scheduler static`` (default) runs the fixed-slot engine,
optionally ``--paged``.
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax
import numpy as np

from repro import obs as obs_mod
from repro.api import Session
from repro.launch import compile_cache
from repro.serve import Request


def run(arch: str, *, n_requests: int = 8, batch_slots: int = 4,
        max_seq: int = 128, prompt_len: int = 16, new_tokens: int = 16,
        scale_down: int = 64, seed: int = 0, mesh=None,
        metrics: Optional[str] = None, paged: bool = False,
        page_size: int = 64, scheduler: str = "static",
        prefill_chunk: int = 32, num_pages: Optional[int] = None):
    # --metrics: stream plan/lower/serve-phase spans + per-request TTFT
    # and queue-wait histograms as JSONL; off -> NULL obs, output
    # unchanged (the spans still annotate any profiler trace).
    obs = obs_mod.Obs(jsonl=metrics, name=f"serve/{arch}") if metrics \
        else obs_mod.NULL
    prev_obs = obs_mod.set_active(obs)
    try:
        return _run(arch, obs, n_requests=n_requests,
                    batch_slots=batch_slots, max_seq=max_seq,
                    prompt_len=prompt_len, new_tokens=new_tokens,
                    scale_down=scale_down, seed=seed, mesh=mesh,
                    metrics=metrics, paged=paged, page_size=page_size,
                    scheduler=scheduler, prefill_chunk=prefill_chunk,
                    num_pages=num_pages)
    finally:
        obs_mod.set_active(prev_obs)
        obs.close()


def synthetic_requests(vocab: int, n: int, prompt_len, new_tokens: int,
                       seed: int = 0):
    """``n`` seeded random-token requests.  ``prompt_len`` is a length or
    an inclusive ``(lo, hi)`` range drawn per request."""
    rng = np.random.default_rng(seed)
    lo, hi = (prompt_len, prompt_len) if isinstance(prompt_len, int) \
        else prompt_len
    reqs = []
    for rid in range(n):
        length = lo if lo == hi else int(rng.integers(lo, hi + 1))
        reqs.append(Request(
            rid=rid, prompt=rng.integers(0, vocab, length, dtype=np.int32),
            max_new_tokens=new_tokens))
    return reqs


def drain(eng, n_submitted: int, max_ticks: int = 10_000):
    """Tick ``eng`` until its queue and slots are empty.

    Returns ``(tokens, tick_seconds)``: the decode tokens produced and
    each tick's wall time (a tick ends on the host's read of the sampled
    tokens, so it includes the device work).  Raises when a request was
    refused or did not finish within ``max_ticks``.
    """
    total = 0
    tick_s = []
    while (eng.queue or any(r is not None for r in eng.active)) \
            and len(tick_s) < max_ticks:
        t0 = time.perf_counter()
        total += eng.step()
        tick_s.append(time.perf_counter() - t0)
    refused = len(getattr(eng, "refused", ()))
    if refused or len(eng.finished) != n_submitted:
        raise RuntimeError(
            f"{len(eng.finished)} of {n_submitted} requests finished "
            f"({refused} refused) after {len(tick_s)} ticks")
    return total, tick_s


def _run(arch: str, obs, *, n_requests, batch_slots, max_seq, prompt_len,
         new_tokens, scale_down, seed, mesh, metrics, paged, page_size,
         scheduler, prefill_chunk, num_pages):
    session = Session(mesh=mesh, obs=obs)
    plan = session.plan(
        arch, batch=batch_slots, seq=max_seq, kind="decode",
        scale_down=scale_down,
        model_kwargs=dict(q_chunk=64, kv_chunk=128, ssd_chunk=32))
    cfg = plan.cfg

    with jax.set_mesh(session.mesh):
        eng = session.serve(plan, batch_slots=batch_slots, max_seq=max_seq,
                            seed=seed, paged=paged, page_size=page_size,
                            scheduler=scheduler,
                            prefill_chunk=prefill_chunk,
                            num_pages=num_pages)
        for req in synthetic_requests(cfg.vocab_size, n_requests,
                                      prompt_len, new_tokens, seed):
            eng.submit(req)
        t0 = time.perf_counter()
        total, tick_s = drain(eng, n_requests)
        ticks = len(tick_s)
        dt = time.perf_counter() - t0
    finished = len(eng.finished)
    print(f"{arch}: {n_requests} requests ({finished} finished), {total} "
          f"tokens in {dt:.2f}s ({total / dt:.1f} tok/s, {ticks} ticks)")
    if obs.enabled:
        session.publish_metrics()
        for name in ("span.serve.tick.s", "serve.ttft_s",
                     "serve.queue_wait_s"):
            s = obs.histogram(name).summary()
            if s.get("count"):
                print(f"{name}: n={s['count']} p50={s['p50'] * 1e3:.1f}ms "
                      f"p99={s['p99'] * 1e3:.1f}ms")
        snap = os.path.join(os.path.dirname(os.path.abspath(metrics)) or ".",
                            "BENCH_serve_metrics.json")
        serve_meta = {
            "scheduler": scheduler, "paged": bool(paged or
                                                  scheduler == "continuous"),
            "page_size": page_size, "prefill_chunk": prefill_chunk,
            "preemptions": obs.counter("serve.preemptions").value,
            "refusals": len(getattr(eng, "refused", ())),
        }
        if hasattr(eng, "blocks"):
            serve_meta["pool_pages"] = eng.blocks.num_pages
            serve_meta["pool_pages_used"] = eng.blocks.used_pages
        obs.snapshot(snap, arch=arch, requests=n_requests,
                     tokens=total, tok_per_s=total / dt, serve=serve_meta)
        print(f"metrics: {metrics}  snapshot: {snap}")
    return total, dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--scale-down", type=int, default=64)
    ap.add_argument("--scheduler", choices=("static", "continuous"),
                    default="static",
                    help="static fixed-slot engine (default) or "
                         "continuous batching over the paged block pool")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache + paged decode kernel for "
                         "the static engine (plain-attention archs)")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prefill chunk tokens (paged/continuous paths)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="continuous pool pages incl. the NULL page "
                         "(default: full static capacity, budget-clamped)")
    ap.add_argument("--metrics", type=str, default=None, metavar="PATH",
                    help="write a JSONL telemetry stream (spans, tick/TTFT/"
                         "queue-wait histograms) to PATH; default off")
    args = ap.parse_args()
    compile_cache.enable()
    run(args.arch, n_requests=args.requests, batch_slots=args.batch_slots,
        max_seq=args.max_seq, new_tokens=args.new_tokens,
        scale_down=args.scale_down, metrics=args.metrics,
        paged=args.paged, page_size=args.page_size,
        scheduler=args.scheduler, prefill_chunk=args.prefill_chunk,
        num_pages=args.num_pages)


if __name__ == "__main__":
    main()
