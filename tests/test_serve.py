"""repro.serve: block manager + scheduler units, engine equivalences.

Fast tests exercise the pure host-side pieces (free-list allocator,
admission verdicts, FIFO/priority scheduling, preempt-requeue).  The
``slow``-marked model tests pin the numerics contracts: static dense ==
static paged == continuous batching, bitwise, under ragged staggered
admission; preemption restarts deterministically; impossible requests
are refused with structured reasons.
"""

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.planner import plan_for
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.serve import (AdmissionRefusal, BlockManager, ContinuousEngine,
                         Engine, NULL_PAGE, PoolExhausted, Request,
                         Scheduler, kv_bytes_per_block)

TINY = ModelConfig(name="serve-tiny", family="dense", n_layers=2,
                   d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128, vocab_size=64)


# ---------------------------------------------------------------------------
# BlockManager (fast, host-only)
# ---------------------------------------------------------------------------

def _bm(num_pages=9, page_size=8, max_seq=64):
    return BlockManager(TINY, num_pages=num_pages, page_size=page_size,
                        max_seq=max_seq)


def test_alloc_free_roundtrip_and_counts():
    bm = _bm()
    assert (bm.capacity_pages, bm.free_pages, bm.used_pages) == (8, 8, 0)
    pages = bm.alloc(rid=1, n_tokens=17)            # ceil(17/8) = 3 pages
    assert len(pages) == 3 and NULL_PAGE not in pages
    assert (bm.free_pages, bm.used_pages, bm.owned(1)) == (5, 3, 3)
    assert bm.free(1) == 3
    assert (bm.free_pages, bm.owned(1)) == (8, 0)
    assert bm.free(1) == 0                          # double-free is a no-op


def test_free_list_reuse_is_lifo():
    bm = _bm()
    first = list(bm.alloc(1, 3 * 8))
    bm.free(1)
    again = list(bm.alloc(2, 3 * 8))
    assert again == first           # hottest pages come back first


def test_table_row_padded_with_null():
    bm = _bm()
    bm.alloc(7, 2 * 8)
    row = bm.table_row(7)
    assert row.shape == (bm.n_row,) and row.dtype == np.int32
    assert NULL_PAGE not in row[:2] and (row[2:] == NULL_PAGE).all()
    assert (bm.null_row() == NULL_PAGE).all()


def test_extend_grows_and_exhausts_atomically():
    bm = _bm(num_pages=4)                           # 3 usable
    bm.alloc(1, 8)
    assert len(bm.extend(1, 16)) == 2
    assert bm.extend(1, 16) is not None             # no growth needed: no-op
    with pytest.raises(PoolExhausted):
        bm.extend(1, 4 * 8)                         # needs 2 more, 1 free
    assert bm.owned(1) == 2 and bm.free_pages == 1  # nothing allocated


def test_admission_refused_beyond_capacity_with_structured_reason():
    bm = _bm(num_pages=4, max_seq=96)               # 3 usable pages
    ref = bm.check_admission(rid=9, prompt_len=30, max_new_tokens=10)
    assert isinstance(ref, AdmissionRefusal)
    assert ref.reason == "pool_capacity"
    assert (ref.needed_tokens, ref.needed_blocks, ref.capacity_blocks) \
        == (40, 5, 3)
    per = kv_bytes_per_block(TINY, 8)
    assert ref.needed_bytes == 5 * per
    assert "pool_capacity" in ref.describe()
    assert ref.to_dict()["reason"] == "pool_capacity"


def test_admission_refused_beyond_seq_window():
    bm = _bm(num_pages=32, max_seq=64)
    ref = bm.check_admission(rid=2, prompt_len=60, max_new_tokens=10)
    assert ref is not None and ref.reason == "seq_window"
    assert bm.check_admission(rid=3, prompt_len=30, max_new_tokens=10) is None


def test_can_admit_is_transient_pressure():
    bm = _bm(num_pages=5)                           # 4 usable
    assert bm.can_admit(prompt_len=16, max_new_tokens=16)
    bm.alloc(1, 24)                                 # 3 pages -> 1 free
    assert not bm.can_admit(prompt_len=16, max_new_tokens=16)
    bm.free(1)
    assert bm.can_admit(prompt_len=16, max_new_tokens=16)


# ---------------------------------------------------------------------------
# Scheduler (fast, host-only)
# ---------------------------------------------------------------------------

def _req(rid, n=8, new=8, priority=0):
    return Request(rid=rid, prompt=np.zeros(n, np.int32),
                   max_new_tokens=new, priority=priority)


def test_scheduler_fifo_and_hol_bypass():
    sched = Scheduler(_bm(num_pages=5), policy="fifo")   # 4 usable pages
    big = _req(0, n=16, new=16)                          # needs 4 pages
    small = _req(1, n=8, new=8)                          # needs 2 pages
    sched.submit(big)
    sched.submit(small)
    sched.blocks.alloc(99, 3 * 8)                        # 1 page free
    assert sched.next_admission() is None                # nobody fits
    sched.blocks.free(99)
    sched.blocks.alloc(98, 8)                            # 3 free: big no, small yes
    got = sched.next_admission()
    assert got is small                                  # documented HOL bypass
    sched.blocks.free(98)
    assert sched.next_admission() is big


def test_scheduler_priority_policy():
    sched = Scheduler(_bm(), policy="priority")
    lo, hi = _req(0, priority=1), _req(1, priority=5)
    sched.submit(lo)
    sched.submit(hi)
    assert sched.next_admission() is hi


def test_scheduler_permanent_refusal_at_submit():
    sched = Scheduler(_bm(num_pages=3), policy="fifo")   # 2 usable pages
    r = _req(5, n=30, new=10)
    sched.submit(r)
    assert r.done and r.refusal is not None
    assert r.refusal.reason == "pool_capacity"
    assert r in sched.refused and not sched.queue


def test_preempt_requeues_front_and_resets():
    sched = Scheduler(_bm(), policy="fifo")
    a, b = _req(0), _req(1)
    sched.submit(a)
    sched.submit(b)
    victim = sched.next_admission()
    assert victim is a
    victim.admit_t, victim.prefill_pos = 123.0, 4
    victim.out.extend([7, 8])
    assert sched.victim([victim, None]) is victim        # youngest admitted
    sched.requeue_preempted(victim)
    assert victim.n_preempted == 1
    assert victim.out == [] and victim.prefill_pos == 0
    assert victim.admit_t is None and victim.first_token_t is None
    assert sched.next_admission() is victim              # FRONT of queue


# ---------------------------------------------------------------------------
# Engine equivalences (slow, tiny model)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def model_params(mesh):
    with jax.set_mesh(mesh):
        plan = plan_for(TINY, mesh)
        model = Model(TINY, mesh, plan, q_chunk=16, kv_chunk=16)
        params = model.init(jax.random.PRNGKey(3))
        params = jax.device_put(params, model.param_shardings())
    return model, params


def _ragged_reqs(n=5):
    rng = np.random.default_rng(0)
    return [Request(rid=r,
                    prompt=rng.integers(0, 64, 3 + 2 * r).astype(np.int32),
                    max_new_tokens=5 + r % 3) for r in range(n)]


@pytest.fixture(scope="module")
def dense_out(mesh, model_params):
    """Greedy streams from the static dense engine — the oracle every
    other engine must match bitwise."""
    model, params = model_params
    with jax.set_mesh(mesh):
        eng = Engine(model, params, batch_slots=2, max_seq=64)
        for r in _ragged_reqs():
            eng.submit(r)
        fin = eng.run()
    assert len(fin) == 5                    # run() returns the finished list
    return {r.rid: list(r.out) for r in fin}


def test_paged_prefill_then_decode_reads_each_layers_own_pages(
        mesh, model_params):
    """Chunked prefill, then greedy decode, through a permuted page table
    of the stacked pool must give the full forward's logits.  The query
    and key weights are scaled up so attention is sharp: a layer that
    wrote or read another layer's K or V rows (the pool holds every layer
    in one array) then moves the logits by 40% of their spread or more,
    against about 1% from bfloat16 rounding."""
    import jax.numpy as jnp

    model, params = model_params
    attn = dict(params["layers"]["attn"])
    attn["wq"], attn["wk"] = attn["wq"] * 8, attn["wk"] * 8
    params = dict(params, layers=dict(params["layers"], attn=attn))
    page, chunk, n_prompt, n_new = 8, 8, 29, 4
    prompt = np.random.default_rng(1).integers(0, 64, n_prompt)
    table = np.array([9, 4, 11, 2, 7, 5], np.int32)   # NULL page 0 unused
    with jax.set_mesh(mesh):
        cache = dict(model.init_paged_pool(12, page),
                     table=jnp.asarray(table[None]))
        pre = jax.jit(model.prefill_chunk_paged)
        dec = jax.jit(model.decode_step_paged)
        for start in range(0, n_prompt, chunk):
            c = np.zeros((1, chunk), np.int32)
            n = min(chunk, n_prompt - start)
            c[0, :n] = prompt[start:start + n]
            logits, cache = pre(params, cache, jnp.asarray(c),
                                jnp.asarray(table),
                                jnp.asarray(start, jnp.int32))
        rows = [np.asarray(logits[0, n - 1], np.float32)]
        toks = list(prompt)
        for i in range(n_new):
            toks.append(int(np.argmax(rows[-1])))
            logits, cache = dec(params, cache,
                                jnp.asarray([[toks[-1]]], jnp.int32),
                                jnp.asarray([n_prompt + i], jnp.int32))
            rows.append(np.asarray(logits[0, 0], np.float32))
        full, _, _ = jax.jit(model.forward)(
            params, jnp.asarray(toks, jnp.int32)[None])
    want = np.asarray(full[0, n_prompt - 1:], np.float32)
    assert np.abs(np.stack(rows) - want).max() <= 0.03 * want.std()


@pytest.mark.slow
def test_static_ragged_matches_solo_oracle(mesh, model_params, dense_out):
    """Per-slot positions: a ragged batched run must equal each request
    decoded alone (the old lockstep max(pos) engine failed this)."""
    model, params = model_params
    with jax.set_mesh(mesh):
        for r in _ragged_reqs():
            solo = Engine(model, params, batch_slots=1, max_seq=64)
            solo.submit(r)
            fin = solo.run()
            assert list(fin[0].out) == dense_out[r.rid], r.rid


@pytest.mark.slow
def test_static_paged_matches_dense(mesh, model_params, dense_out):
    model, params = model_params
    with jax.set_mesh(mesh):
        eng = Engine(model, params, batch_slots=2, max_seq=64, paged=True,
                     page_size=8, prefill_chunk=4)
        for r in _ragged_reqs():
            eng.submit(r)
        fin = eng.run()
    assert {r.rid: list(r.out) for r in fin} == dense_out


@pytest.mark.slow
def test_continuous_matches_static_paged_bitwise(mesh, model_params,
                                                 dense_out):
    """Same jitted ops, physically-permuted pages: continuous batching
    must reproduce the static engines token-for-token."""
    model, params = model_params
    with jax.set_mesh(mesh):
        eng = ContinuousEngine(model, params, batch_slots=2, max_seq=64,
                               page_size=8, prefill_chunk=4)
        for r in _ragged_reqs():
            eng.submit(r)
        fin = eng.run()
    assert {r.rid: list(r.out) for r in fin} == dense_out


@pytest.mark.slow
def test_continuous_recycles_slots_beyond_batch(mesh, model_params,
                                                dense_out):
    """7 requests through 2 slots in ONE run — dynamic admission must
    retire-and-refill without tearing down the engine."""
    model, params = model_params
    with jax.set_mesh(mesh):
        eng = ContinuousEngine(model, params, batch_slots=2, max_seq=64,
                               page_size=8, prefill_chunk=4)
        reqs = _ragged_reqs(7)
        for r in reqs:
            eng.submit(r)
        fin = eng.run()
    assert len(fin) == 7 > eng.B
    for r in fin:
        if r.rid in dense_out:
            assert list(r.out) == dense_out[r.rid], r.rid


@pytest.mark.slow
def test_preemption_requeues_and_completes(mesh, model_params):
    """Pool of 4 usable pages, two sequences that each grow to 3 pages:
    conservative admission lets both in against shared headroom, lazy
    growth collides, the youngest is preempted — and the greedy restart
    must still finish both with full streams."""
    model, params = model_params
    with jax.set_mesh(mesh):
        eng = ContinuousEngine(model, params, batch_slots=2, max_seq=64,
                               page_size=8, num_pages=5, prefill_chunk=4)
        reqs = [Request(rid=100 + i, prompt=np.arange(6, dtype=np.int32) + i,
                        max_new_tokens=12) for i in range(2)]
        for r in reqs:
            eng.submit(r)
        fin = eng.run()

        assert len(fin) == 2
        assert sum(r.n_preempted for r in fin) >= 1
        for r in fin:
            assert len(r.out) == 12
            solo = ContinuousEngine(model, params, batch_slots=1, max_seq=64,
                                    page_size=8, prefill_chunk=4)
            solo.submit(Request(rid=r.rid, prompt=np.asarray(r.prompt),
                                max_new_tokens=12))
            assert list(solo.run()[0].out) == list(r.out), r.rid


@pytest.mark.slow
def test_impossible_request_structurally_refused(mesh, model_params):
    model, params = model_params
    with jax.set_mesh(mesh):
        eng = ContinuousEngine(model, params, batch_slots=2, max_seq=64,
                               page_size=8, num_pages=3)
        big = Request(rid=999, prompt=np.arange(40, dtype=np.int32),
                      max_new_tokens=20)
        eng.submit(big)
        assert big.done and big.refusal is not None
        assert big.refusal.reason == "pool_capacity"
        assert big.refusal.needed_blocks > big.refusal.capacity_blocks
        assert eng.run() == [] and big in eng.refused
