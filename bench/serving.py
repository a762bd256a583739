"""What the two serve traffic modules share: building the engine on seeded
weights, ticking it while timing every output token, and comparing the
served tokens with the reference.

The engine is ``Session.serve(scheduler="continuous")`` with greedy
sampling.  A tick is one ``ContinuousEngine.step``; it ends when the
program has read its sampled tokens back, so the host clock after it is
the moment those tokens exist.  Every token a request gets in a tick is
stamped with the tick's end.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, weights
from bench.reference import dense_gqa

#: reference lengths are padded to these multiples, so that a few
#: compiled programs serve every request
SEQ_BUCKET, ROW_BUCKET = 2048, 256


def mesh(ctx):
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(devices=ctx.devices)


def build(ctx, mesh, slots: int, max_seq: int, chunk: int):
    """(session, engine) on the seeded weights; call inside
    ``jax.set_mesh(mesh)``."""
    from repro.api import Session

    session = Session(mesh=mesh)
    plan = session.plan(ctx.cfg, batch=slots, seq=max_seq, kind="decode")
    params = weights.make(ctx.shape, ctx.seed,
                          shardings=plan.model.param_shardings())
    weights.check_against(params, plan.model.param_sds())
    session.put("serve/params", params, kind="params")
    eng = session.serve(plan, batch_slots=slots, max_seq=max_seq,
                        seed=ctx.seed, scheduler="continuous",
                        prefill_chunk=chunk)
    return session, eng


def free(session) -> None:
    session.evict("serve/kv_pool")
    session.evict("serve/params")


class Ticker:
    """Ticks the engine and stamps every output token.

    ``times[rid]`` lists the host time of each token a request got.
    With ``detail`` on, each tick also records the work it did, for the
    per-layer metrics: the context length of every decoded slot, every
    prefill chunk as (start, tokens, last), and the kernel's per-slot
    lengths.
    """

    def __init__(self, ctx, eng):
        self.ctx, self.eng = ctx, eng
        self.times: Dict[int, List[float]] = {}
        self.ticks: List[dict] = []
        self.n_finished = 0

    def tick(self, detail: bool = False) -> Tuple[float, int]:
        """One engine step; returns (its end time, tokens it made)."""
        eng = self.eng
        before = {r.rid: (r.prefill_pos, len(r.out))
                  for r in eng.active if r is not None} if detail else None
        t0 = time.perf_counter()
        with self.ctx.span("tick"):
            eng.step()
        t1 = time.perf_counter()
        done = eng.finished[self.n_finished:]
        self.n_finished = len(eng.finished)
        made = 0
        decode, prefill = [], []
        for r in [r for r in eng.active if r is not None] + done:
            seen = self.times.setdefault(r.rid, [])
            new = len(r.out) - len(seen)
            if new > 0:
                seen.extend([t1] * new)
                made += new
            if detail:
                pp0, n0 = before.get(r.rid, (0, 0))
                P = len(r.prompt)
                if r.prefill_pos > pp0:
                    prefill.append((pp0, r.prefill_pos - pp0,
                                    r.prefill_pos >= P))
                if pp0 >= P and n0 and len(r.out) > n0:
                    decode.append(P + n0)
        if detail:
            self.ticks.append({
                "s": t1 - t0, "decode": decode, "prefill": prefill,
                "slots": decode + [1] * (eng.B - len(decode))})
        return t1, made


def itl(times: Dict[int, List[float]], t0: float, close: float
        ) -> List[float]:
    """Gaps between consecutive tokens of a request, for every token made
    inside the window (t0, close]."""
    out = []
    for ts in times.values():
        out.extend(b - a for a, b in zip(ts, ts[1:]) if t0 < b <= close)
    return out


def tokens_in(times: Dict[int, List[float]], t0: float, close: float) -> int:
    return sum(1 for ts in times.values() for t in ts if t0 < t <= close)


def sample(finished: List[tuple], seed: int, tokens: int, at_most: int,
           longest=lambda po: len(po[1])) -> List[tuple]:
    """A seeded sample of (prompt, out) pairs with the longest (by
    ``longest``, default the output) in it, grown until it holds
    ``tokens`` served tokens or ``at_most`` requests."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -longest(finished[i]))
    rest = list(np.random.default_rng(int(seed) % 2**64).permutation(
        order[1:]))
    pick = [order[0]]
    while rest and len(pick) < at_most and \
            sum(len(finished[i][1]) for i in pick) < tokens:
        pick.append(int(rest.pop()))
    return [finished[i] for i in pick]


def _pad(x: np.ndarray, mult: int, value: int) -> np.ndarray:
    n = -(-len(x) // mult) * mult
    return np.concatenate([x, np.full(n - len(x), value, x.dtype)])


def reference_gaps(ctx, seqs: List[tuple], control: bool = False
                   ) -> Dict[str, float]:
    """Widest reference gap of the served tokens of ``seqs`` ((prompt,
    out) pairs) and, with ``control``, of the tokens the float8 control
    puts first at the same positions."""
    s = ctx.shape
    w = weights.make(s, ctx.seed)
    widest = {"served_gap": 0.0}
    if control:
        widest["control_gap"] = 0.0
    n_tok = 0
    with jax.default_matmul_precision("highest"):
        for prompt, out in seqs:
            out = np.asarray(out, np.int32)
            toks = np.concatenate([np.asarray(prompt, np.int32), out])
            rows = np.arange(len(prompt) - 1, len(toks) - 1, dtype=np.int32)
            tp = jnp.asarray(_pad(toks, SEQ_BUCKET, 0))
            rp = jnp.asarray(_pad(rows, ROW_BUCKET, int(rows[0])))
            z = np.asarray(dense_gqa.row_logits(w, tp, rp, s))[:len(rows)]
            widest["served_gap"] = max(widest["served_gap"], float(
                compare.served_gap(z, out).max()))
            if control:
                zc = np.asarray(dense_gqa.row_logits(
                    w, tp, rp, s, dense_gqa.fp8))[:len(rows)]
                widest["control_gap"] = max(widest["control_gap"], float(
                    compare.served_gap(z, zc.argmax(-1)).max()))
            n_tok += len(out)
    widest["compared_tokens"] = n_tok
    return widest
