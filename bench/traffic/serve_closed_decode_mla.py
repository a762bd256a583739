"""Closed long-context decode on a latent-attention MoE configuration:
every slot holds a long prompt, prefilled in set-up, and decodes through
the whole window.

Mix parameters as ``serve_closed_decode`` (``slots``, ``max_seq``,
``prefill_chunk``, ``prompt_min``, ``prompt_max``, ``sample_requests``),
plus ``page_size`` (the latent pool's page) and ``prefill_wave`` (set-up
submits the prompts this many at a time and waits for each tick, so that
at most that many prefill chunks' float32 logits are in flight beside
the weights and the pool).

The engine is ``Session.serve(scheduler="continuous")`` on the seeded
weights of ``bench/reference/deepseek_v2_weights.py``, with telemetry off
as in the other serve cells.  The decode steps count the tokens routed
to each held expert on the device; the record keeps the count's change
over the window, read back before and after it.  The
served tokens are compared with ``bench/reference/deepseek_v2.py`` as
``serve_closed_decode`` compares them with the dense reference.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, flops_mla, serving
from bench.reference import deepseek_v2, deepseek_v2_weights
from bench.traffic.serve_closed_decode import prompts


def program_config(ctx):
    """The program's config; a program without the arch fails here, at
    once, with a KeyError."""
    try:
        return ctx.cfg
    except ImportError as e:
        raise KeyError(f"the program has no arch {ctx.conf['arch']!r}") \
            from e


def build(ctx, cfg, s, mesh, mix):
    from repro.api import Session

    session = Session(mesh=mesh)
    plan = session.plan(cfg, batch=mix["slots"], seq=mix["max_seq"],
                        kind="decode")
    params = deepseek_v2_weights.make(
        s, ctx.seed, shardings=plan.model.param_shardings())
    deepseek_v2_weights.check_against(params, plan.model.param_sds())
    session.put("serve/params", params, kind="params")
    eng = session.serve(plan, batch_slots=mix["slots"],
                        max_seq=mix["max_seq"], seed=ctx.seed,
                        scheduler="continuous",
                        prefill_chunk=mix["prefill_chunk"],
                        page_size=mix["page_size"])
    return session, eng


def reference_gaps(ctx, s, seqs, control=False):
    """``serving.reference_gaps`` with the MLA reference."""
    w = deepseek_v2_weights.make(s, ctx.seed)
    widest = {"served_gap": 0.0}
    if control:
        widest["control_gap"] = 0.0
    n_tok = 0
    with jax.default_matmul_precision("highest"):
        for prompt, out in seqs:
            out = np.asarray(out, np.int32)
            toks = np.concatenate([np.asarray(prompt, np.int32), out])
            rows = np.arange(len(prompt) - 1, len(toks) - 1, dtype=np.int32)
            tp = jnp.asarray(serving._pad(toks, serving.SEQ_BUCKET, 0))
            rp = jnp.asarray(serving._pad(rows, serving.ROW_BUCKET,
                                          int(rows[0])))
            z = np.asarray(deepseek_v2.row_logits(w, tp, rp, s))[:len(rows)]
            widest["served_gap"] = max(widest["served_gap"], float(
                compare.served_gap(z, out).max()))
            if control:
                zc = np.asarray(deepseek_v2.row_logits(
                    w, tp, rp, s, deepseek_v2.fp8))[:len(rows)]
                widest["control_gap"] = max(widest["control_gap"], float(
                    compare.served_gap(z, zc.argmax(-1)).max()))
            n_tok += len(out)
    widest["compared_tokens"] = n_tok
    return widest


def run(ctx) -> dict:
    from repro.serve import Request

    mix = ctx.mix
    cfg = program_config(ctx)
    s = deepseek_v2.shape_of(ctx.conf)
    mesh = serving.mesh(ctx)
    with jax.set_mesh(mesh):
        session, eng = build(ctx, cfg, s, mesh, mix)
        reqs = [Request(rid=i, prompt=p,
                        max_new_tokens=mix["max_seq"] - 1 - len(p))
                for i, p in enumerate(prompts(mix, s.vocab, ctx.seed))]
        ticker = serving.Ticker(ctx, eng)
        wave = mix["prefill_wave"]
        for i in range(0, len(reqs), wave):
            for r in reqs[i:i + wave]:
                eng.submit(r)
            while not all(r.out and r.prefill_pos >= len(r.prompt)
                          for r in reqs[:i + wave]):
                if eng.refused or any(r.done for r in reqs):
                    raise RuntimeError("a long-context request was refused "
                                       "or ended in set-up")
                ticker.tick()
                # a tick with no slot decoding reads nothing back: without
                # this wait the wave's chunks pile up in flight, each with
                # its (1, chunk, vocab) float32 logits
                jax.block_until_ready(eng.cache)
        ticker.tick()                    # one step of pure decode
        held0 = eng.held_tokens()
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
        held = eng.held_tokens() - held0
        ctx.read_memory_peak()
        decoding = sum(1 for r in eng.active if r is not None)
        seqs = [(r.prompt, list(r.out)) for r in reqs]
        record = {
            "attempted": made, "failed": 0, "window_s": end - t0,
            "out_tokens": serving.tokens_in(ticker.times, t0, end),
            "itl_s": serving.itl(ticker.times, t0, end),
            "traced_ticks": ticker.ticks,
            "held_tokens": held.tolist(),
            "mla": ctx.conf,
            "latent_bytes_per_token": s.latent_bytes_per_token,
            "held_weight_bytes": flops_mla.weight_bytes(s),
        }
        serving.free(session)
        del eng, session, ticker
        gc.collect()
    pick = serving.sample(seqs, ctx.seed, tokens=1 << 30,
                          at_most=mix["sample_requests"],
                          longest=lambda po: len(po[0]))
    gaps = reference_gaps(ctx, s, pick, control=ctx.calibrate)
    ctx.log(f"compare: {len(pick)} slots, {gaps['compared_tokens']} served "
            f"tokens, widest gap {gaps['served_gap']!r}")
    record["checks"] = [ctx.check("served_gap", gaps["served_gap"]),
                        ctx.check("unserved", mix["slots"] - decoding)]
    record["compared_tokens"] = gaps["compared_tokens"]
    if ctx.calibrate:
        record["calibration"] = {
            "control": {"served_gap": gaps["control_gap"]}}
    return record
