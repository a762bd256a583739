"""Training at a fixed token batch: ``Session.step`` on fresh seeded
batches for the whole window.

Mix parameters: ``seq`` (tokens per row), ``batch_per_chip`` (rows each
chip takes), ``microbatches`` (null: the program's default), ``comms``
(the ``Session.plan`` gradient-sync routing), ``adamw`` (the optimizer
as configured: lr, b1, b2, eps, weight_decay, grad_clip), ``data``
(``n_patterns`` and ``pattern_len`` of the structured token stream) and
``checked_steps`` (how many first steps the reference follows),
``check_memory`` (whether ``Session.plan`` may refuse the step on its
memory model's estimate) and ``ahead_s`` (how many seconds of steps the
window keeps dispatched ahead of the one it waits for, as timed on the
last checked step, so that a host stall shorter than that leaves the
chip fed).

Set-up builds one compiled step with its state from the seed and drives
it through the checked steps with the window's own call and feed; their
losses, the optimizer's first moment after step 1 (its norms, and the
whole of it copied to the host) and the master weights after the last
are read on the way.  The same object then runs the
window.  When its time is up it dispatches nothing more, waits for every
step sent and reads the clock after that wait: all of those steps count,
over all of that time.  ``attempted`` counts the window's steps.
"""

from __future__ import annotations

import collections
import gc
import time

import jax
import numpy as np

from bench import compare, weights
from bench.reference import train as ref_train


class StructuredLM:
    """Seeded token rows made of patterns from a seeded pool.

    After the program's ``data.pipeline.SyntheticLM(structured=True)``,
    which repeats one pattern along a whole row.  Here a row strings
    together as many patterns as it needs, each drawn once in the batch
    (no pattern repeats within a row or across its rows), at a random
    phase.  So the two halves of a row, and the rows of a batch, hold
    different tokens: a fault that leaves part of the batch out changes
    the gradient, and no token id comes back hundreds of times in a row.
    """

    def __init__(self, vocab: int, batch: int, seq: int, seed: int,
                 n_patterns: int, pattern_len: int):
        self.per_row = -(-(seq + 1) // pattern_len) + 1
        if batch * self.per_row > n_patterns:
            raise ValueError(f"{batch} rows of {self.per_row} patterns "
                             f"need more than {n_patterns} patterns")
        self.batch, self.seq, self.pattern_len = batch, seq, pattern_len
        self.rng = np.random.default_rng(int(seed) % 2**64)
        self.patterns = self.rng.integers(0, vocab, (n_patterns, pattern_len),
                                          dtype=np.int32)

    def next(self) -> dict:
        pick = self.rng.choice(len(self.patterns), self.batch * self.per_row,
                               replace=False)
        rows = self.patterns[pick].reshape(self.batch, -1)
        phase = self.rng.integers(0, self.pattern_len, self.batch)
        toks = np.stack([r[p:p + self.seq + 1] for r, p in zip(rows, phase)])
        return {"tokens": toks[:, :-1].copy(), "labels": toks[:, 1:].copy()}


def optimizer(mix: dict):
    from repro.train import AdamWConfig
    o = mix["adamw"]
    return AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                       weight_decay=o["weight_decay"],
                       grad_clip=o["grad_clip"])


def setup(ctx, session, plan, data, shardings):
    """Put the seeded state on the device and run the checked steps.
    Returns (the program's readings, the host batches they took)."""
    from repro.train import optimizer as opt_mod

    mix = ctx.mix
    params = weights.make(ctx.shape, ctx.seed,
                          shardings=plan.model.param_shardings())
    weights.check_against(params, plan.model.param_sds())
    session.put("train_state", {
        "params": params,
        "opt": opt_mod.init_state(params, plan.model.param_specs(),
                                  session.mesh)}, kind="train_state")
    del params
    losses, batches, grad, grad_full = [], [], None, None
    for i in range(mix["checked_steps"]):
        b = data.next()
        batches.append(b)
        t = time.perf_counter()
        with ctx.span("step"):
            m = session.step(plan, jax.device_put(b, shardings))
        losses.append(float(m["loss"]))
        step_s = time.perf_counter() - t
        if i == 0:
            mu = session.get("train_state")["opt"]["mu"]
            grad = compare.norms(mu, 1.0 / (1.0 - mix["adamw"]["b1"]))
            grad_full = compare.host_leaves(
                mu, 1.0 / (1.0 - mix["adamw"]["b1"]))
            del mu
    init = weights.make(ctx.shape, ctx.seed,
                        shardings=plan.model.param_shardings())
    change = compare.diff_norms(session.get("train_state")["opt"]["master"],
                                init)
    del init
    return {"losses": losses, "grad": grad, "grad_full": grad_full,
            "change": change, "step_s": step_s}, batches


def run(ctx) -> dict:
    from repro.api import Session
    from repro.launch.mesh import make_host_mesh

    mix, chips = ctx.mix, len(ctx.devices)
    rows, seq = mix["batch_per_chip"] * chips, mix["seq"]
    session = Session(mesh=make_host_mesh(devices=ctx.devices))
    plan = session.plan(ctx.cfg, batch=rows, seq=seq, comms=mix["comms"],
                        adamw=optimizer(mix),
                        microbatches=mix.get("microbatches"),
                        check_memory=mix["check_memory"])
    _, shardings = plan.batch_specs()
    data = StructuredLM(ctx.shape.vocab, rows, seq, ctx.seed,
                        mix["data"]["n_patterns"], mix["data"]["pattern_len"])
    with jax.set_mesh(session.mesh):
        prog, batches = setup(ctx, session, plan, data, shardings)
        ctx.log(f"train: plan path={plan.path} microbatches="
                f"{plan.num_microbatches} rows={rows} seq={seq}; checked "
                f"losses {prog['losses']}")

        ahead = max(1, int(mix["ahead_s"] / prog["step_s"]))
        ctx.log(f"train: {ahead} steps dispatched ahead (a step took "
                f"{prog['step_s']!r} s in set-up)")
        ctx.window_started()
        t0 = time.perf_counter()
        steps, pending = 0, collections.deque()
        while True:
            b = jax.device_put(data.next(), shardings)
            with ctx.span("step"):
                pending.append(session.step(plan, b))
            if len(pending) > ahead:
                jax.block_until_ready(pending.popleft())
            steps += 1
            ctx.trace_at(time.perf_counter() - t0)
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        jax.block_until_ready(list(pending))
        window_s = time.perf_counter() - t0
        ctx.window_closed()
        last_loss = float(pending[-1]["loss"])
        ctx.read_memory_peak()
        session.evict("train_state")
        del pending, b
    gc.collect()

    ref = ref_train.run(ctx.shape, ctx.seed, batches, mix["adamw"],
                        chips=chips)
    nums = compare.train_numbers(prog, ref)
    for name, (val, where) in nums.items():
        ctx.log(f"compare {name}: {val!r} at {where}")
    ctx.log(f"compare losses program {prog['losses']} reference "
            f"{ref['losses']}; window's last loss {last_loss}")
    calibration = None
    if ctx.calibrate:
        alts = {"control": dict(cast="fp8"),
                "half_batch": dict(fault="half_batch")}
        if chips > 1:
            alts["no_exchange"] = dict(fault="no_exchange")
        calibration = {}
        for alt, kw in alts.items():
            got = ref_train.run(ctx.shape, ctx.seed, batches, mix["adamw"],
                                chips=chips, **kw)
            calibration[alt] = {n: v for n, (v, _) in
                                compare.train_numbers(got, ref).items()}
    return {
        "calibration": calibration,
        "attempted": steps, "failed": 0,
        "steps": steps, "tokens": steps * rows * seq, "window_s": window_s,
        "checks": [ctx.check(n, v) for n, (v, _) in nums.items()],
    }
