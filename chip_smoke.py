#!/usr/bin/env python3
"""Smoke run of the main path on TPU: qwen2-0.5b at its full published
width and depth (24 layers, d_model 896, vocab 151936), random weights
from ``--seed``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, data-parallel train only

One chip runs three phases through the user's entry points:

- train: ``Session.plan`` with the default ``comms="auto"``,
  ``init_state`` and 5 ``Session.step``\\ s on one batch of structured
  synthetic tokens.
  Losses must be finite and falling, and the first must equal the first
  loss of the ``comms="off"`` step on the same batch.
- serve: ``Session.serve(scheduler="continuous")`` with 8 slots and
  max_seq 2048 answers 16 seeded requests (prompts of 128-1024 tokens,
  64 new tokens each).  Every request must finish, none refused.
- kernel: the paged decode as dispatched (the Pallas kernel on TPU)
  against ``kernels.ref.paged_decode_attention`` at the model's widths,
  and the latent (MLA) paged decode against
  ``kernels.ref.paged_decode_latent_attention`` at DeepSeek-V2-Lite's
  (16 heads over a 512 + 64 latent, 32 slots of 8192 positions).

``--chips 4`` runs only the data-parallel train path on the host's four
chips (mesh data=4, model=1, explicit comms gradient sync) and compares
its losses with the same batch and seed on one of the chips.  The chip
takes the batch as four microbatches of one sequence: like each of the
four chips, it then computes one sequence's bf16 gradient at a time and
sums them in fp32, so only the order of that sum differs.

Times printed here are smoke timings, not benchmark results.  A failed
check exits non-zero.  The last line of stdout, printed only when every
phase passed, is ``{"ok": true, "device": {...}}``.  Everything runs in
this one process: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen2-0.5b"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 2, 1024, 5
DP_BATCH, DP_SEQ, DP_STEPS = 4, 512, 3
SLOTS, MAX_SEQ, N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, 2048, 16, \
    (128, 1024), 64

#: comms="auto" vs "off" on one chip run the same math through different
#: programs (an outer shard_map around the loss); only fusion and
#: reduction order may differ.
COMMS_RTOL = 2e-3
#: one chip (four microbatches) vs four chips: the same per-sequence
#: gradients, summed in fp32 by a scan on one side and an all-reduce on
#: the other; nothing else changes.
DP_RTOL = 2e-3
#: paged decode, bf16 inputs, fp32 softmax in both paths, bf16 output: a
#: couple of bf16 ulps (2^-7 at magnitude 1) of summation-order slack.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
#: latent decode: the kernel feeds bfloat16 query and probabilities to the
#: matmuls (the oracle keeps float32): 2% of the output's largest value,
#: as the CPU test in interpret mode (tests/test_deepseek_v2.py)
LATENT_TOL = 2e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"ok: {what}", flush=True)


def peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def _adamw(steps: int):
    from repro.train import AdamWConfig, warmup_cosine
    # the launch/train.py schedule
    return AdamWConfig(lr=warmup_cosine(3e-3, steps // 10 + 1, steps))


def train(session, *, batch: int, seq: int, steps: int, seed: int,
          comms: str = "auto", microbatches=None):
    """``steps`` Session steps on the first batch of the seeded
    structured stream, repeated: a few steps must fit one batch, while a
    fresh batch of unseen random patterns each step is not learnable in
    five.

    Returns ``(plan, losses, step_seconds, placement)``; ``placement``
    counts the train-state leaves and the devices they occupy.  The state
    is evicted afterwards so the next phase has the device to itself.
    """
    from repro.data import SyntheticLM

    plan = session.plan(ARCH, batch=batch, seq=seq, comms=comms,
                        microbatches=microbatches, adamw=_adamw(steps))
    b = jax.tree.map(jnp.asarray, next(iter(SyntheticLM(
        plan.cfg.vocab_size, batch, seq, seed=seed, structured=True))))
    losses, seconds = [], []
    with jax.set_mesh(session.mesh):
        state = session.init_state(plan, seed=seed)
        leaves = jax.tree.leaves(state)
        placement = {
            "leaves": len(leaves),
            "devices": len(set().union(*(x.sharding.device_set
                                         for x in leaves))),
            "on_every_device": sum(
                len(x.sharding.device_set) == session.mesh.size
                for x in leaves),
            "sharded": sum(not x.sharding.is_fully_replicated
                           for x in leaves)}
        del state, leaves
        for _ in range(steps):
            t0 = time.perf_counter()
            metrics = session.step(plan, b)
            losses.append(float(jax.device_get(metrics["loss"])))
            seconds.append(time.perf_counter() - t0)
        session.evict("train_state")
    return plan, losses, seconds, placement


def _report_steps(tag: str, plan, losses, seconds) -> None:
    print(f"{tag}: path={plan.path} microbatches={plan.num_microbatches} "
          f"losses={losses}")
    steady = (f"{statistics.median(seconds[1:]):.4f} s" if len(seconds) > 1
              else "n/a")
    print(f"{tag}: smoke timing (not a benchmark): first step incl. "
          f"compile {seconds[0]:.2f} s, steady step median {steady}")


def train_phase(session, seed: int) -> None:
    kw = dict(batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=seed)
    plan_off, off, off_s, _ = train(session, steps=1, comms="off", **kw)
    _report_steps("train comms=off", plan_off, off, off_s)
    plan, losses, secs, placement = train(session, steps=TRAIN_STEPS, **kw)
    _report_steps("train comms=auto", plan, losses, secs)
    print(f"train: state placement {placement}")
    check(plan.path == "comms",
          f"comms=auto takes the explicit comms path (got {plan.path})")
    check(all(np.isfinite(losses)), "train losses are finite")
    check(losses[-1] < losses[0],
          f"loss falls: {losses[-1]:.4f} < {losses[0]:.4f}")
    rel = abs(losses[0] - off[0]) / abs(off[0])
    check(rel <= COMMS_RTOL,
          f"first loss comms=auto {losses[0]!r} vs comms=off {off[0]!r}: "
          f"rel diff {rel:.2e} <= {COMMS_RTOL}")


def serve_phase(session, seed: int) -> None:
    from repro.launch import serve as serve_mod

    plan = session.plan(ARCH, batch=SLOTS, seq=MAX_SEQ, kind="decode")
    with jax.set_mesh(session.mesh):
        eng = session.serve(plan, batch_slots=SLOTS, max_seq=MAX_SEQ,
                            seed=seed, scheduler="continuous")
        reqs = serve_mod.synthetic_requests(
            plan.cfg.vocab_size, N_REQUESTS, PROMPT_LENS, NEW_TOKENS, seed)
        for r in reqs:
            eng.submit(r)
        tokens, tick_s = serve_mod.drain(eng, len(reqs))
    print(f"serve: {len(eng.finished)}/{len(reqs)} finished, {tokens} "
          f"decode tokens, {len(tick_s)} ticks, prompt lengths "
          f"{sorted(len(r.prompt) for r in reqs)}")
    print(f"serve: smoke timing (not a benchmark): first tick incl. "
          f"compile {tick_s[0]:.2f} s, tick median "
          f"{statistics.median(tick_s[1:]):.4f} s, all ticks "
          f"{sum(tick_s):.2f} s")
    check(len(eng.finished) == len(reqs) and not eng.refused,
          f"all {len(reqs)} requests finished, none refused")
    check(all(len(r.out) == NEW_TOKENS for r in eng.finished),
          f"every request produced {NEW_TOKENS} tokens")
    session.evict("serve/kv_pool")
    session.evict("serve/params")


def kernel_phase(cfg, seed: int) -> None:
    from repro.kernels import ops as kops
    from repro.kernels import ref

    page = 64
    n_row = MAX_SEQ // page
    n_pages = 1 + SLOTS * n_row
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (SLOTS, Hq, hd), jnp.bfloat16)
    # a two-layer stacked pool, read at layer 1
    pool = (2, n_pages, page, Hkv * hd)
    k_pages = jax.random.normal(kk, pool, jnp.bfloat16)
    v_pages = jax.random.normal(kv, pool, jnp.bfloat16)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(1 + rng.permutation(n_pages - 1).reshape(
        SLOTS, n_row), jnp.int32)
    lens = jnp.asarray(rng.integers(1, MAX_SEQ + 1, SLOTS), jnp.int32)
    layer = jnp.asarray(1, jnp.int32)

    got = jax.jit(kops.paged_decode_attention)(q, k_pages, v_pages, table,
                                                lens, layer)
    want = jax.jit(ref.paged_decode_attention)(q, k_pages, v_pages, table,
                                               lens, layer)
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    decision = kops.dispatch_report()["ops"]["paged_decode_attention"]
    err = float(np.max(np.abs(got - want)))
    print(f"kernel: paged decode B={SLOTS} Hq={Hq} Hkv={Hkv} hd={hd} "
          f"page={page} max|pallas-ref|={err!r}")
    check(decision["active"] and decision["mode"] == "pallas",
          f"paged decode dispatches to the Pallas kernel ({decision})")
    check(np.allclose(got, want, rtol=KERNEL_RTOL, atol=KERNEL_ATOL),
          f"paged decode matches the reference (atol=rtol={KERNEL_ATOL})")


def latent_kernel_phase(seed: int) -> None:
    """The latent decode kernel at DeepSeek-V2-Lite's widths, read at
    layer 1 of a two-layer pool with pages out of order and ragged
    lengths, against the gather oracle."""
    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.kernels import paged_attention, ref
    from repro.models import mla

    cfg = get_config("deepseek-v2-lite")
    slots, max_seq, page = 32, 8192, 256
    r, dr, H = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.n_heads
    pack = paged_attention.rope_pack(dr)
    n_row = max_seq // page
    n_pages = 1 + slots * n_row
    kq, kc, kp = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (slots, H, r + dr), jnp.float32)
    c = jax.random.normal(kc, (2, n_pages, page, r), jnp.bfloat16)
    pe = jax.random.normal(kp, (2, n_pages, page // pack, pack * dr),
                           jnp.bfloat16)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(1 + rng.permutation(n_pages - 1).reshape(
        slots, n_row), jnp.int32)
    lens = jnp.asarray(rng.integers(1, max_seq + 1, slots), jnp.int32)
    args = (q, c, pe, table, lens, jnp.asarray(1, jnp.int32))
    scale = mla.softmax_scale(cfg)
    got = np.asarray(jax.jit(functools.partial(
        kops.paged_decode_latent_attention, scale=scale))(*args))
    want = np.asarray(jax.jit(functools.partial(
        ref.paged_decode_latent_attention, scale=scale))(*args))
    err = float(np.max(np.abs(got - want)))
    top = float(np.max(np.abs(want)))
    print(f"kernel: latent paged decode B={slots} H={H} r={r} dr={dr} "
          f"page={page} max|pallas-ref|={err!r} of max|ref|={top!r}")
    check(kops.resolve("paged_decode_latent_attention") == "pallas",
          "latent paged decode dispatches to the Pallas kernel")
    check(err <= LATENT_TOL * top,
          f"latent paged decode matches the reference ({err!r} <= "
          f"{LATENT_TOL} x {top!r})")


def one_chip(seed: int) -> None:
    from repro.api import Session
    from repro.kernels import ops as kops

    dev = jax.devices()[0]
    session = Session()
    print(f"session: {session.describe()}")
    t0 = time.perf_counter()
    train_phase(session, seed)
    print(f"train: phase {time.perf_counter() - t0:.1f} s, device peak "
          f"{peak_gib(dev)}")
    t0 = time.perf_counter()
    serve_phase(session, seed)
    print(f"serve: phase {time.perf_counter() - t0:.1f} s, device peak "
          f"{peak_gib(dev)}")
    from repro.configs import get_config
    kernel_phase(get_config(ARCH), seed)
    latent_kernel_phase(seed)
    print("dispatch:", json.dumps(kops.dispatch_report(), default=str))


def four_chips(seed: int) -> None:
    from repro.api import Session
    from repro.launch.mesh import make_host_mesh

    devs = jax.devices()
    check(len(devs) == 4, f"four devices attached (got {len(devs)})")
    kw = dict(batch=DP_BATCH, seq=DP_SEQ, steps=DP_STEPS, seed=seed)
    sess4 = Session(mesh=make_host_mesh())
    plan4, l4, s4, place4 = train(sess4, **kw)
    _report_steps("train 4 chips", plan4, l4, s4)
    print(f"train 4 chips: mesh {dict(sess4.mesh.shape)}, state placement "
          f"{place4}")
    del sess4
    sess1 = Session(mesh=make_host_mesh(devices=devs[:1]))
    plan1, l1, s1, _ = train(sess1, microbatches=DP_BATCH, **kw)
    _report_steps("train 1 chip", plan1, l1, s1)
    check(plan4.path == "comms",
          f"4-chip plan takes the explicit comms path (got {plan4.path})")
    check(place4["devices"] == 4
          and place4["on_every_device"] == place4["leaves"],
          f"every train-state leaf spans all 4 devices ({place4})")
    check(plan1.num_microbatches == DP_BATCH,
          f"1-chip plan takes one sequence per microbatch "
          f"(got {plan1.num_microbatches})")
    check(all(np.isfinite(l4)), "4-chip losses are finite")
    rel = [abs(a - b) / abs(b) for a, b in zip(l4, l1)]
    check(max(rel) <= DP_RTOL,
          f"4-chip vs 1-chip losses: rel diff per step "
          f"{[f'{r:.2e}' for r in rel]} <= {DP_RTOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU attached (JAX reports {dev.platform!r})",
              file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    print(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}, compile cache {compile_cache.enable()}")
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
