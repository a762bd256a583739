"""Closed long-context decode: every slot holds a long prompt, prefilled
in set-up, and decodes through the whole window.

Mix parameters: ``slots``, ``max_seq``, ``prefill_chunk`` (the engine),
``prompt_min`` and ``prompt_max`` (prompt lengths, drawn stratified
uniform: one from each of ``slots`` equal slices, in a seeded order),
``sample_requests`` (how many slots the reference checks, the longest
among them).

Each request may run to ``max_seq - 1`` positions, so none ends inside
the window.  The prefill is set-up the traffic needs: the window starts
when every slot decodes.  ``attempted`` counts the tokens decoded in the
window.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import serving


def prompts(mix: dict, vocab: int, seed: int):
    rng = np.random.default_rng(int(seed) % 2**64)
    n, lo, hi = mix["slots"], mix["prompt_min"], mix["prompt_max"]
    u = (rng.permutation(n) + rng.random(n)) / n
    lens = lo + np.floor(u * (hi - lo + 1)).astype(np.int64)
    return [rng.integers(0, vocab, int(n_), dtype=np.int32) for n_ in lens]


def run(ctx) -> dict:
    from repro.serve import Request

    mix = ctx.mix
    mesh = serving.mesh(ctx)
    with jax.set_mesh(mesh):
        session, eng = serving.build(ctx, mesh, mix["slots"],
                                     mix["max_seq"], mix["prefill_chunk"])
        reqs = [Request(rid=i, prompt=p,
                        max_new_tokens=mix["max_seq"] - 1 - len(p))
                for i, p in enumerate(prompts(mix, ctx.shape.vocab,
                                              ctx.seed))]
        for r in reqs:
            eng.submit(r)
        ticker = serving.Ticker(ctx, eng)
        while not all(r.out and r.prefill_pos >= len(r.prompt)
                      and not r.done for r in reqs):
            if eng.refused or any(r.done for r in reqs):
                raise RuntimeError("a long-context request was refused or "
                                   "ended in set-up")
            ticker.tick()
        ticker.tick()                    # one step of pure decode
        ctx.window_started()
        t0 = time.perf_counter()
        made = 0
        while True:
            tracing = ctx.trace_at(time.perf_counter() - t0)
            end, n = ticker.tick(detail=tracing)
            made += n
            if end - t0 >= ctx.seconds:
                break
        ctx.window_closed()
        ctx.read_memory_peak()
        decoding = sum(1 for r in eng.active if r is not None)
        seqs = [(r.prompt, list(r.out)) for r in reqs]
        record = {
            "attempted": made, "failed": 0, "window_s": end - t0,
            "out_tokens": serving.tokens_in(ticker.times, t0, end),
            "itl_s": serving.itl(ticker.times, t0, end),
            "traced_ticks": ticker.ticks,
        }
        serving.free(session)
        del eng, session, ticker
        gc.collect()
    pick = serving.sample(seqs, ctx.seed, tokens=1 << 30,
                          at_most=mix["sample_requests"],
                          longest=lambda po: len(po[0]))
    gaps = serving.reference_gaps(ctx, pick, control=ctx.calibrate)
    ctx.log(f"compare: {len(pick)} slots, {gaps['compared_tokens']} served "
            f"tokens, widest gap {gaps['served_gap']!r}")
    record["checks"] = [ctx.check("served_gap", gaps["served_gap"]),
                        ctx.check("unserved", mix["slots"] - decoding)]
    record["compared_tokens"] = gaps["compared_tokens"]
    if ctx.calibrate:
        record["calibration"] = {
            "control": {"served_gap": gaps["control_gap"]}}
    return record
