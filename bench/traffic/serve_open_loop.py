"""Open-loop chat traffic: Poisson arrivals at a fixed rate with
lognormal prompt and output lengths, clipped.

Mix parameters: ``slots``, ``max_seq``, ``prefill_chunk`` (the engine),
``rate_per_s``, ``prompt`` and ``output`` (``median``, ``sigma`` of the
log, ``min``, ``max``), ``block`` (how many consecutive requests a seed
may reorder among themselves), ``ramp_s`` (how long the arrivals run before the
window opens, so that it measures a loaded server and not an empty one;
set-up the traffic needs), ``drain_s`` (how long after the window's close
the requests due in it may take to start), ``sample_tokens`` and
``sample_max`` (the correctness sample, drawn from every request that
finished).

Every seed gets the same work in another order: the inter-arrival gaps,
prompt lengths and output lengths are fixed quantiles of their
distributions, the same set for every seed, and a seed reorders them
only within blocks of ``block`` consecutive requests that each span the
distribution (:func:`stratified`), so no seed draws a busier stretch
than another.  The token ids are drawn from the seed.  Arrivals go on
during the drain so that the load stays as it was.

A request is due at its arrival time and is handed to the engine at the
first tick boundary after it; its time to first token and its queue wait
count from when it was due.  How late the generator handed requests over
is printed on standard error.  ``attempted`` counts the requests due in
the window; ``failed`` those refused or without a first token by the
drain's end (a late token is late, not missing).
"""

from __future__ import annotations

import statistics
import gc
import time

import jax
import numpy as np

from bench import serving
from bench.harness import percentile

WARMUP_PROMPTS = 2


def stratified(rng, n: int, block: int) -> np.ndarray:
    """The n quantiles (i + 0.5) / n, the same set for every seed, in a
    seeded order that moves each one only within its block of ``block``
    consecutive draws.  The blocks are dealt so that each spans the whole
    distribution (block k takes one quantile of each run of as many
    neighbours as there are blocks, the same one for every seed), so every
    seed offers the same work in every stretch of the run, in another
    order."""
    nb = -(-n // block)
    fixed = np.random.default_rng(0)
    groups = [fixed.permutation(np.arange(g, min(g + nb, n)))
              for g in range(0, n, nb)]
    out = []
    for k in range(nb):
        members = np.array([grp[k] for grp in groups if k < len(grp)])
        out.extend(rng.permutation(members))
    return (np.asarray(out) + 0.5) / n


def lognormal(u: np.ndarray, p: dict) -> np.ndarray:
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(min(max(x, 1e-9), 1 - 1e-9)) for x in u])
    x = np.round(p["median"] * np.exp(p["sigma"] * z))
    return np.clip(x, p["min"], p["max"]).astype(np.int64)


def arrivals(mix: dict, vocab: int, seed: int, seconds: float):
    """[(due s, prompt, max_new)] for the ramp, the window and the drain,
    due times counted from the ramp's start."""
    rng = np.random.default_rng(int(seed) % 2**64)
    rate = mix["rate_per_s"]
    out, t0 = [], 0.0
    for span in (mix["ramp_s"], seconds, mix["drain_s"]):
        n = max(1, int(round(rate * span)))
        block = mix["block"]
        gaps = -np.log1p(-stratified(rng, n, block)) / rate
        due = t0 + np.cumsum(gaps)
        p_len = lognormal(stratified(rng, n, block), mix["prompt"])
        o_len = lognormal(stratified(rng, n, block), mix["output"])
        for d, lp, lo in zip(due, p_len, o_len):
            out.append((float(d), rng.integers(0, vocab, int(lp),
                                               dtype=np.int32), int(lo)))
        t0 = t0 + span
    return out


def warm_up(eng, mix: dict, vocab: int) -> None:
    """Compile and run every program the window uses: a prompt over two
    chunks and a few decode steps each, then an idle engine."""
    from repro.serve import Request

    rng = np.random.default_rng(0)
    for i in range(WARMUP_PROMPTS):
        eng.submit(Request(rid=-1 - i, prompt=rng.integers(
            0, vocab, mix["prefill_chunk"] + 3 + i, dtype=np.int32),
            max_new_tokens=4))
    while eng.queue or any(r is not None for r in eng.active):
        eng.step()
    eng.finished.clear()


def backlog_thirds(samples, seconds: float) -> dict:
    """Mean queue (requests handed over and not yet admitted) over the
    window's first and last thirds: the sweep's test of a growing
    backlog."""
    out = {}
    for name, lo, hi in (("backlog_first", 0, seconds / 3),
                         ("backlog_last", 2 * seconds / 3, seconds)):
        v = [b for t, b in samples if lo <= t < hi]
        out[name] = sum(v) / len(v) if v else 0.0
    return out


def run(ctx) -> dict:
    from repro.serve import Request

    mix = ctx.mix
    reqs = arrivals(mix, ctx.shape.vocab, ctx.seed, ctx.seconds)
    ramp = mix["ramp_s"]
    first = sum(1 for d, _, _ in reqs if d < ramp)
    last = sum(1 for d, _, _ in reqs if d < ramp + ctx.seconds)
    mesh = serving.mesh(ctx)
    with jax.set_mesh(mesh):
        session, eng = serving.build(ctx, mesh, mix["slots"],
                                     mix["max_seq"], mix["prefill_chunk"])
        warm_up(eng, mix, ctx.shape.vocab)
        ticker = serving.Ticker(ctx, eng)
        objs, late, backlog = [], [], []
        t_ramp = time.perf_counter()
        t0, close, i = None, None, 0
        while True:
            el = time.perf_counter() - t_ramp
            while i < len(reqs) and reqs[i][0] <= el:
                due, prompt, max_new = reqs[i]
                r = Request(rid=i, prompt=prompt, max_new_tokens=max_new)
                r.submit_t = t_ramp + due
                with ctx.span("submit"):
                    eng.submit(r)
                objs.append(r)
                late.append(el - due)
                i += 1
            if t0 is None and el >= ramp:
                ctx.window_started()
                t0 = t_ramp + ramp
            busy = eng.queue or any(r is not None for r in eng.active)
            if busy:
                tracing = t0 is not None and ctx.trace_at(el - ramp)
                end, _ = ticker.tick(detail=tracing)
                if t0 is not None and close is None:
                    backlog.append((end - t0, len(eng.queue)))
            elif i < len(reqs):
                time.sleep(max(0.0, min(reqs[i][0], ramp) - el
                               if t0 is None else reqs[i][0] - el))
                end = time.perf_counter()
            else:
                end = time.perf_counter()
            if t0 is not None and close is None \
                    and end - t0 >= ctx.seconds:
                close = end
                ctx.window_closed()
            if close is not None:
                window = objs[first:last]
                if len(window) == last - first and all(
                        r.rid in ticker.times or r.done for r in window):
                    break
                if end - close >= mix["drain_s"] or \
                        i >= len(reqs) and not busy:
                    break
        ctx.read_memory_peak()
        done = {r.rid for r in eng.finished}
        window = objs[first:last]
        finished = [(r.prompt, list(r.out)) for r in objs
                    if r.rid in done]
        failed = sum(1 for r in window if r.rid not in ticker.times)
        gave_up = time.perf_counter()
        ttft, wait = [], []
        untraced = t0 + (ctx.trace_began if ctx.trace_began is not None
                         else ctx.seconds)
        for r in window:
            ts = ticker.times.get(r.rid)
            ttft.append((ts[0] if ts else gave_up) - r.submit_t)
            if r.admit_t is not None and r.submit_t < untraced:
                wait.append(r.admit_t - r.submit_t)
        record = {
            "attempted": last - first, "failed": failed,
            "window_s": close - t0,
            "out_tokens": serving.tokens_in(ticker.times, t0, close),
            "itl_s": serving.itl(ticker.times, t0, close),
            "ttft_s": ttft, "queue_wait_s": wait,
            "traced_ticks": ticker.ticks,
        }
        record.update(backlog_thirds(backlog, ctx.seconds))
        serving.free(session)
        del eng, session, ticker
        gc.collect()
    ctx.log(f"generator: {len(late)} requests handed over, late by p50 "
            f"{percentile(late, 50)!r} s, p99 {percentile(late, 99)!r} s, "
            f"max {max(late)!r} s")
    seqs = serving.sample(finished, ctx.seed, mix["sample_tokens"],
                          mix["sample_max"])
    gaps = serving.reference_gaps(ctx, seqs, control=ctx.calibrate)
    ctx.log(f"compare: {len(seqs)} requests, {gaps['compared_tokens']} "
            f"served tokens, widest gap {gaps['served_gap']!r}")
    record["checks"] = [ctx.check("served_gap", gaps["served_gap"]),
                        ctx.check("unserved", failed)]
    record["compared_tokens"] = gaps["compared_tokens"]
    if ctx.calibrate:
        record["calibration"] = {
            "control": {"served_gap": gaps["control_gap"]}}
    return record
