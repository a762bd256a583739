"""Batched serving engines: prefill + decode with persistent KV state.

The serving analogue of dMath's master/worker split: the engine (master)
admits requests and issues jitted steps; all tensor state (params, caches)
is persistent in device memory (§2.1) — nothing crosses the host boundary
per token except the sampled ids.

Two schedulers share the jitted steps and the retirement path:

- :class:`Engine` — static batching.  A fixed B-slot cache is allocated
  once; finished slots are refilled from the queue and their cache rows
  re-prefilled.  Every slot decodes at its OWN position (``pos`` is a
  per-slot vector, not a lockstep max), so ragged prompts admitted in the
  same batch leave no KV gaps.
- :class:`ContinuousEngine` — continuous batching over a block-paged KV
  pool (``repro.serve.blocks``) with a budget-governed request scheduler
  (``repro.serve.scheduler``): per-tick admission, chunked prefill
  interleaved with decode, lazy page growth with preempt-and-requeue on
  pool exhaustion, and page recycling so one run admits far more
  sequences than ``batch_slots``.

Both paged paths prefill through the SAME jitted chunk function
(``Model.prefill_chunk_paged``) and decode through the same paged kernel,
so greedy outputs are bit-identical between them: attention gathers pages
in logical order, making the math invariant to the physical page
permutation the allocator happens to choose.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod

from .blocks import NULL_PAGE, BlockManager, PoolExhausted, \
    kv_bytes_per_block, pool_pages_for_budget
from .scheduler import DeadlineExceeded, Request, Scheduler

__all__ = ["Engine", "ContinuousEngine", "Request"]


def _make_prefill_fn(model):
    """Prefill-one-slot step closing over the MODEL only.

    A free function (not an Engine method) on purpose: the jitted
    callable may outlive its engine in a Session's compiled-artifact
    cache, and a bound method would pin that engine's params and full KV
    cache for the cache's lifetime.
    """

    def prefill_slot(params, cache, tokens, slot):
        """Prefill one request into cache row ``slot`` (B=1 forward)."""
        logits, c1 = model.prefill(params, tokens)

        def write(full, one):
            # one: (L, 1, S, ...) -> pad S to T, write at [.., slot, ..]
            pad = [(0, 0)] * one.ndim
            pad[2] = (0, full.shape[2] - one.shape[2])
            if one.ndim >= 3 and full.shape[2] != one.shape[2] \
                    and full.ndim == one.ndim:
                one = jnp.pad(one, pad)
            return jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot, axis=1)

        cache = jax.tree.map(write, cache, c1)
        return logits[:, -1, :], cache

    return prefill_slot


def _retire(engine, b: int) -> Request:
    """THE retirement path, shared by both engines: release the slot's
    storage, stamp the request, collect it on ``engine.finished``."""
    req = engine.active[b]
    engine._release_slot(req, b)
    req.done = True
    req.finish_t = time.perf_counter()
    engine.finished.append(req)
    engine.active[b] = None
    engine.pos[b] = 0
    return req


class Engine:
    """Static-batch engine: fixed slots, per-slot positions."""

    def __init__(self, model, params, batch_slots: int, max_seq: int,
                 temperature: float = 0.0, seed: int = 0,
                 opcache=None, registry=None, cache_key: str = None,
                 obs=None, paged: bool = False, page_size: int = 64,
                 prefill_chunk: int = 32):
        self.obs = obs if obs is not None else obs_mod.NULL
        self.model = model
        self.params = params
        self.B = batch_slots
        self.T = max_seq
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)

        # paged: the KV cache is a pool of fixed-size pages addressed
        # through an indices table — decode attends via the paged kernel
        # instead of scanning the dense (B, T) cache, and prefill runs
        # through the chunked paged path (shared with ContinuousEngine).
        self.paged = paged
        self.page_size = page_size
        self.prefill_chunk = min(prefill_chunk, max_seq)
        if paged:
            self.cache = model.init_paged_cache(batch_slots, max_seq,
                                                page_size)
        else:
            self.cache = model.init_cache(batch_slots, max_seq)
        self.pos = np.zeros(batch_slots, np.int32)
        self.active: List[Optional[Request]] = [None] * batch_slots
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.refused: List[Request] = []     # deadline-shed queued work

        # ``opcache`` (a repro.core.opcache.OpCache, normally the owning
        # Session's) makes the jitted steps shared compiled artifacts: a
        # second engine on the same model/slots replays them by id instead
        # of re-tracing.
        def _jit(op, build):
            if opcache is None:
                return build()
            mesh = getattr(model, "mesh", None)
            key = opcache.key_for(
                op, (), mesh_shape=(tuple(mesh.shape.items())
                                    if hasattr(mesh, "shape") else ()),
                model=id(model), B=batch_slots, T=max_seq,
                paged=paged, page=page_size, chunk=self.prefill_chunk)
            return opcache.get_or_build(key, op, build)

        if paged:
            self._decode = _jit("serve_decode_paged", lambda: jax.jit(
                model.decode_step_paged, donate_argnums=(1,)))
            self._prefill_chunk_fn = _jit(
                "serve_prefill_chunk", lambda: jax.jit(
                    model.prefill_chunk_paged, donate_argnums=(1,)))
        else:
            self._decode = _jit("serve_decode", lambda: jax.jit(
                model.decode_step, donate_argnums=(1,)))
            self._prefill_one = _jit("serve_prefill", lambda: jax.jit(
                _make_prefill_fn(model)))

        # Optional write-through to a Session's persistent-state registry:
        # the fixed-size cache is allocated ONCE (bytes never change), so
        # the per-tick refresh swaps buffers without re-walking the tree.
        self._registry = registry
        self._cache_key = cache_key
        if registry is not None and cache_key is not None:
            registry.put(cache_key, self.cache, kind="kv_cache")

    def _publish_cache(self):
        if self._registry is not None and self._cache_key is not None:
            self._registry.replace_value(self._cache_key, self.cache)

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        if req.submit_t is None:
            req.submit_t = time.perf_counter()
        self.queue.append(req)

    def _prefill_chunks(self, row, prompt) -> jax.Array:
        """Run a prompt through the shared chunked paged prefill; returns
        the logits of the final chunk (1, C, V)."""
        C = self.prefill_chunk
        P = len(prompt)
        logits = None
        for start in range(0, P, C):
            chunk = np.zeros((1, C), np.int32)
            n = min(C, P - start)
            chunk[0, :n] = prompt[start:start + n]
            logits, self.cache = self._prefill_chunk_fn(
                self.params, self.cache, jnp.asarray(chunk), row,
                jnp.asarray(start, jnp.int32))
        return logits, (P - 1) % C if P % C else C - 1 if P else 0

    def _shed_expired(self):
        """Deadline TTL for queued work (admitted slots always finish):
        expired requests leave with a structured DeadlineExceeded."""
        now = time.perf_counter()
        for req in [r for r in self.queue if r.expired(now)]:
            self.queue.remove(req)
            req.refusal = DeadlineExceeded(
                rid=req.rid, reason="deadline",
                deadline_s=float(req.deadline_s),
                waited_s=now - req.submit_t,
                n_preempted=req.n_preempted)
            req.done = True
            req.finish_t = now
            self.refused.append(req)
            self.obs.counter("serve.deadline_shed").inc()

    def _admit(self):
        self._shed_expired()
        nb = -(-self.T // self.page_size) if self.paged else 0
        for b in range(self.B):
            if self.active[b] is None and self.queue:
                req = self.queue.pop(0)
                req.admit_t = time.perf_counter()
                if self.paged:
                    # slot-major page ownership: slot b's table row is
                    # constant, prefill streams the prompt through the
                    # shared chunk function
                    row = self.cache["table"][b]
                    last, idx = self._prefill_chunks(row, req.prompt)
                    last_logits = last[:, idx, :]
                else:
                    toks = jnp.asarray(req.prompt, jnp.int32)[None]
                    last_logits, self.cache = self._prefill_one(
                        self.params, self.cache, toks,
                        jnp.asarray(b, jnp.int32))
                nxt = self._sample(last_logits)[0]
                req.out.append(int(nxt))
                req.first_token_t = time.perf_counter()
                self.active[b] = req
                self.pos[b] = len(req.prompt)
        self._publish_cache()

    def _sample(self, logits):
        if self.temperature == 0.0:
            return np.asarray(jnp.argmax(logits, -1))
        self.key, k = jax.random.split(self.key)
        return np.asarray(jax.random.categorical(
            k, logits / self.temperature, axis=-1))

    def _release_slot(self, req: Request, b: int):
        pass                        # fixed rows: nothing to free

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine tick: admit, decode one token for every active slot."""
        self._admit()
        if not any(r is not None for r in self.active):
            return 0
        tokens = np.zeros((self.B, 1), np.int32)
        for b, r in enumerate(self.active):
            if r is not None:
                tokens[b, 0] = r.out[-1]
        # per-slot positions: every slot decodes at its OWN position —
        # ragged prompts admitted together leave no KV gaps (idle slots
        # park at 0; their garbage write is overwritten by the next
        # prefill before anything attends it)
        pos = jnp.asarray(self.pos)
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(tokens), pos)
        self._publish_cache()
        nxt = self._sample(logits[:, 0, :])
        n_active = 0
        for b, r in enumerate(self.active):
            if r is None:
                continue
            r.out.append(int(nxt[b]))
            self.pos[b] += 1
            n_active += 1
            if len(r.out) >= r.max_new_tokens or self.pos[b] >= self.T - 1:
                _retire(self, b)
        return n_active

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        ticks = 0
        while (self.queue or any(r is not None for r in self.active)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return list(self.finished)


class ContinuousEngine:
    """Continuous batching over a block-paged KV pool.

    Per tick: admit from the scheduler while slots AND pool headroom
    allow, run ONE prefill chunk for every mid-prefill sequence, grow
    page tables lazily for the decode-ready set (preempting the youngest
    sequence on pool exhaustion), then decode one token for every ready
    slot at its own position.  Finished sequences retire through the
    shared :func:`_retire` path and their pages recycle into the free
    list — one run admits far more sequences than ``batch_slots``.

    The page pool is registered in the session's persistent-state
    registry (``{name}/kv_pool``), so an over-budget pool is refused at
    construction with the same :class:`~repro.api.errors.PlanMemoryError`
    the planner uses for OOM train plans; per-request admission refusals
    carry the block manager's structured footprint reasons.
    """

    def __init__(self, model, params, batch_slots: int, max_seq: int,
                 temperature: float = 0.0, seed: int = 0,
                 opcache=None, registry=None, cache_key: str = None,
                 obs=None, page_size: int = 64,
                 num_pages: Optional[int] = None, prefill_chunk: int = 32,
                 policy: str = "fifo"):
        self.obs = obs if obs is not None else obs_mod.NULL
        self.model = model
        self.params = params
        self.B = batch_slots
        self.T = max_seq
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.page_size = page_size
        self.prefill_chunk = min(prefill_chunk, max_seq)

        cfg = model.cfg
        n_row = -(-max_seq // page_size)
        if num_pages is None:
            # full static capacity (+ the NULL page), clamped to the
            # registry's remaining budget — the footprint model governs
            # the pool size the same way it governs train plans
            num_pages = 1 + batch_slots * n_row
            if registry is not None and registry.capacity is not None:
                headroom = registry.capacity - registry.total_bytes()
                num_pages = min(num_pages, pool_pages_for_budget(
                    headroom, cfg, page_size))
        self.blocks = BlockManager(cfg, num_pages=num_pages,
                                   page_size=page_size, max_seq=max_seq)
        self.sched = Scheduler(self.blocks, policy=policy)

        pool = model.init_paged_pool(num_pages, page_size)
        self._table_np = np.full((batch_slots, n_row), NULL_PAGE, np.int32)
        self._table_dirty = True
        self.cache: Dict[str, jax.Array] = dict(
            pool, table=jnp.asarray(self._table_np))
        self.pos = np.zeros(batch_slots, np.int32)
        self.active: List[Optional[Request]] = [None] * batch_slots
        self.finished: List[Request] = []
        # fault/chaos seams: called as hook(tick) at the top of every
        # step() — repro.faults.arm_engine registers pool storms here
        self.tick_hooks: List[Callable[[int], None]] = []
        self._tick = 0

        def _jit(op, build):
            if opcache is None:
                return build()
            mesh = getattr(model, "mesh", None)
            key = opcache.key_for(
                op, (), mesh_shape=(tuple(mesh.shape.items())
                                    if hasattr(mesh, "shape") else ()),
                model=id(model), B=batch_slots, T=max_seq,
                paged=True, page=page_size, chunk=self.prefill_chunk)
            return opcache.get_or_build(key, op, build)

        # SAME ops (and opcache keys) as the static paged engine: both
        # engines replay one compiled artifact set per (model, B, T)
        self._decode = _jit("serve_decode_paged", lambda: jax.jit(
            model.decode_step_paged, donate_argnums=(1,)))
        self._prefill_chunk_fn = _jit(
            "serve_prefill_chunk", lambda: jax.jit(
                model.prefill_chunk_paged, donate_argnums=(1,)))

        # the pool is ONE registry entry: footprint-accounted, refused
        # with a PlanMemoryError when it does not fit the budget
        self._registry = registry
        self._cache_key = cache_key
        if registry is not None and cache_key is not None:
            registry.put(cache_key, self.cache, kind="kv_cache")

    # ------------------------------------------------------------------
    @property
    def queue(self) -> List[Request]:
        return list(self.sched.queue)

    @property
    def refused(self) -> List[Request]:
        return list(self.sched.refused)

    @property
    def shed(self) -> List[Request]:
        """Queued requests shed on deadline (structured DeadlineExceeded)."""
        return list(self.sched.shed)

    def submit(self, req: Request):
        self.sched.submit(req)

    def held_tokens(self) -> Optional[np.ndarray]:
        """Tokens the decode steps have routed to each held expert of each
        MoE layer, ``(MoE layers, held experts)``, read back from the
        device counter the steps keep in the cache; None where the model
        holds no expert share.  Waits for the work in flight."""
        held = self.cache.get("held_tokens")
        return None if held is None else np.asarray(held, np.int64)

    def _publish_cache(self):
        if self._registry is not None and self._cache_key is not None:
            self._registry.replace_value(self._cache_key, self.cache)

    def _sample(self, logits) -> np.ndarray:
        """The token each row samples, read back to the host; callers
        hold a ``serve.sample`` span around the slice of ``logits`` and
        this."""
        if self.temperature == 0.0:
            ids = jnp.argmax(logits, -1)
        else:
            self.key, k = jax.random.split(self.key)
            ids = jax.random.categorical(
                k, logits / self.temperature, axis=-1)
        with self.obs.span("serve.readback"):
            return np.asarray(ids)

    def _release_slot(self, req: Request, b: int):
        self.blocks.free(req.rid)
        self._table_np[b] = NULL_PAGE
        self._table_dirty = True

    # ------------------------------------------------------------------
    def _admit(self):
        for req in self.sched.shed_expired():
            self.obs.counter("serve.deadline_shed").inc()
        now = time.perf_counter
        for b in range(self.B):
            if self.active[b] is not None:
                continue
            req = self.sched.next_admission()
            if req is None:
                break
            # admission reserved prompt+max_new headroom; only the prompt
            # pages are taken now — decode growth allocates lazily
            self.blocks.alloc(req.rid, len(req.prompt))
            req.admit_t = now()
            if self.obs.enabled:
                self.obs.histogram("serve.queue_wait_s").observe(
                    req.admit_t - req.submit_t)
            req.prefill_pos = 0
            self.active[b] = req
            self.pos[b] = 0

    def _prefill_tick(self):
        """ONE chunk for every mid-prefill sequence (interleaved with
        decode ticks, so long prompts never starve running decodes)."""
        C = self.prefill_chunk
        for b, req in enumerate(self.active):
            if req is None or req.prefill_pos >= len(req.prompt):
                continue
            P = len(req.prompt)
            start = req.prefill_pos
            n = min(C, P - start)
            with self.obs.span("serve.prefill"):
                chunk = np.zeros((1, C), np.int32)
                chunk[0, :n] = req.prompt[start:start + n]
                row = jnp.asarray(self.blocks.table_row(req.rid))
                logits, self.cache = self._prefill_chunk_fn(
                    self.params, self.cache, jnp.asarray(chunk), row,
                    jnp.asarray(start, jnp.int32))
            req.prefill_pos = start + n
            if req.prefill_pos >= P:      # final chunk: first token
                with self.obs.span("serve.sample"):
                    nxt = self._sample(logits[:, n - 1, :])[0]
                req.out.append(int(nxt))
                req.first_token_t = time.perf_counter()
                if self.obs.enabled:
                    self.obs.histogram("serve.ttft_s").observe(
                        req.first_token_t - req.submit_t)
                self.pos[b] = P
                self._table_np[b] = self.blocks.table_row(req.rid)
                self._table_dirty = True

    def _preempt(self, victim: Request):
        """Free the victim's pages and requeue it at the FRONT (full
        restart: greedy decode regenerates the same tokens).  The
        scheduler's cycle bound may instead convert a request that keeps
        circulating into the permanent structured refusal."""
        vb = next(b for b, r in enumerate(self.active) if r is victim)
        self.blocks.free(victim.rid)
        self._table_np[vb] = NULL_PAGE
        self._table_dirty = True
        self.active[vb] = None
        self.pos[vb] = 0
        refusal = self.sched.requeue_preempted(victim)
        self.obs.counter("serve.preemptions").inc()
        if refusal is not None:
            self.obs.counter("serve.preempt_refused").inc()

    def _extend_or_preempt(self, ready: List[int]) -> List[int]:
        """Grow tables so every ready slot can write ``pos[b]``; on pool
        exhaustion preempt the youngest admitted sequence and retry."""
        for b in list(ready):
            req = self.active[b]
            if req is None:                   # preempted by an earlier
                continue                      # slot's extend this tick
            while True:
                if req is not self.active[b]:
                    break                     # b itself was preempted
                try:
                    before = self.blocks.owned(req.rid)
                    self.blocks.extend(req.rid, int(self.pos[b]) + 1)
                    if self.blocks.owned(req.rid) != before:
                        self._table_np[b] = self.blocks.table_row(req.rid)
                        self._table_dirty = True
                    break
                except PoolExhausted:
                    victim = self.sched.victim(self.active)
                    self._preempt(victim)
        return [b for b in ready if self.active[b] is not None]

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine tick: admit, prefill one chunk each, extend/preempt,
        decode one token for every ready slot, retire finished.

        Each phase runs under a span (``serve.tick`` around the whole
        step; inside it ``serve.admit``, one ``serve.prefill`` per chunk,
        ``serve.extend``, ``serve.decode``, and ``serve.sample`` holding
        ``serve.readback``), which a profiler trace shows as
        ``repro.serve.*`` with telemetry on or off."""
        with self.obs.span("serve.tick"):
            return self._step()

    def _step(self) -> int:
        for hook in self.tick_hooks:
            hook(self._tick)
        self._tick += 1
        with self.obs.span("serve.admit"):
            self._admit()
        self._prefill_tick()
        ready = [b for b, r in enumerate(self.active)
                 if r is not None and r.prefill_pos >= len(r.prompt)]
        with self.obs.span("serve.extend"):
            ready = self._extend_or_preempt(ready)
        n_ready = len(ready)
        if n_ready:
            with self.obs.span("serve.decode"):
                if self._table_dirty:
                    self.cache = dict(self.cache,
                                      table=jnp.asarray(self._table_np))
                    self._table_dirty = False
                tokens = np.zeros((self.B, 1), np.int32)
                pos = np.zeros(self.B, np.int32)
                for b in ready:
                    tokens[b, 0] = self.active[b].out[-1]
                    pos[b] = self.pos[b]
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(pos))
            with self.obs.span("serve.sample"):
                nxt = self._sample(logits[:, 0, :])
            for b in ready:
                r = self.active[b]
                r.out.append(int(nxt[b]))
                self.pos[b] += 1
                if len(r.out) >= r.max_new_tokens \
                        or self.pos[b] >= self.T - 1:
                    _retire(self, b)
        self._publish_cache()
        if self.obs.enabled:
            self.obs.gauge("serve.pool_blocks_used").set(
                self.blocks.used_pages)
        return n_ready

    def run(self, max_ticks: int = 10_000) -> List[Request]:
        ticks = 0
        while (self.sched.queue
               or any(r is not None for r in self.active)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return list(self.finished)
