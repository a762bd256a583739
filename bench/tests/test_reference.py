"""The float32 reference against the program at a tiny size on the CPU:
the full forward's logits, and prefill then decode through the paged
cache.  Both program paths compute in bfloat16 with float32
accumulation, so they may differ from float32 by bfloat16 rounding
carried through two layers: the tolerance is 3% of the logits' spread
(bfloat16 keeps 8 bits, 0.4% per rounding)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model_config, weights
from bench.reference import dense_gqa
from bench.tests import tiny

TOL = 0.03


@pytest.fixture(params=["tiny-qwen2", "tiny-qwen3"])
def conf(request):
    return {"tiny-qwen2": tiny.TINY_QWEN2,
            "tiny-qwen3": tiny.TINY_QWEN3}[request.param]


def model_and_weights(conf, seed=3):
    from repro.launch.mesh import make_host_mesh
    from repro.models import Model

    s = model_config.shape_of(conf)
    cfg = model_config.program_config(conf)
    mesh = make_host_mesh(devices=jax.devices()[:1])
    model = Model(cfg, mesh)
    return s, model, mesh, weights.make(s, seed)


def ref_logits(w, s, toks, rows):
    with jax.default_matmul_precision("highest"):
        return np.asarray(dense_gqa.row_logits(
            w, jnp.asarray(toks), jnp.asarray(rows), s))


def test_reference_matches_forward(conf):
    s, model, mesh, w = model_and_weights(conf)
    toks = np.random.default_rng(0).integers(0, s.vocab, 48, dtype=np.int32)
    with jax.set_mesh(mesh):
        got, _, _ = jax.jit(model.forward)(w, jnp.asarray(toks)[None])
    got = np.asarray(got[0], np.float32)
    want = ref_logits(w, s, toks, np.arange(48))
    assert np.abs(got - want).max() <= TOL * want.std()
    assert np.abs(got - want).max() > 0          # the paths differ at all


def test_reference_matches_paged_prefill_then_decode(conf):
    s, model, mesh, w = model_and_weights(conf)
    page, chunk, P = 16, 16, 37
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, s.vocab, P, dtype=np.int32)
    with jax.set_mesh(mesh):
        pool = model.init_paged_pool(9, page)
        table = np.arange(1, 9, dtype=np.int32)
        cache = dict(pool, table=jnp.asarray(table[None]))
        pre = jax.jit(model.prefill_chunk_paged)
        dec = jax.jit(model.decode_step_paged)
        for start in range(0, P, chunk):
            c = np.zeros((1, chunk), np.int32)
            n = min(chunk, P - start)
            c[0, :n] = prompt[start:start + n]
            logits, cache = pre(w, cache, jnp.asarray(c), jnp.asarray(table),
                                jnp.asarray(start, jnp.int32))
        rows = [np.asarray(logits[0, n - 1], np.float32)]
        toks = list(prompt)
        for i in range(6):
            nxt = int(np.argmax(rows[-1]))
            toks.append(nxt)
            logits, cache = dec(w, cache, jnp.asarray([[nxt]], jnp.int32),
                                jnp.asarray([P + i], jnp.int32))
            rows.append(np.asarray(logits[0, 0], np.float32))
    got = np.stack(rows)
    want = ref_logits(w, s, np.asarray(toks, np.int32),
                      np.arange(P - 1, P + 6))
    assert np.abs(got - want).max() <= TOL * want.std()


def test_tied_head_is_the_transposed_embedding():
    s = model_config.shape_of(tiny.TINY_QWEN2)
    w = weights.make(s, 5)
    tied = dataclasses.replace(s, tied=True)
    w_tied = {k: v for k, v in w.items() if k != "unembed"}
    w_untied = dict(w, unembed=w["embed"].T)
    toks = np.arange(10, dtype=np.int32)
    a = ref_logits(w_tied, tied, toks, np.arange(10))
    b = ref_logits(w_untied, s, toks, np.arange(10))
    np.testing.assert_array_equal(a, b)
    assert set(weights.layout(tied)) == {"embed", "final_norm", "layers"}


@pytest.mark.parametrize("rows", [1, 4])
def test_half_batch_fault_runs_and_moves_the_loss(rows):
    """The reference with half of the batch left out (half of the tokens
    where the batch is one row) runs and reads another loss and gradient
    than the sound reference."""
    from bench.reference import train as ref_train

    s = model_config.shape_of(tiny.TINY_QWEN2)
    opt = tiny.MIXES["tiny-train"]["adamw"]
    rng = np.random.default_rng(5)
    toks = rng.integers(0, s.vocab, (2, rows, 33), dtype=np.int32)
    batches = [{"tokens": t[:, :-1], "labels": t[:, 1:]} for t in toks]
    sound = ref_train.run(s, 5, batches, opt)
    half = ref_train.run(s, 5, batches, opt, fault="half_batch")
    assert len(half["losses"]) == 2
    assert abs(half["losses"][0] - sound["losses"][0]) > 1e-4
    assert half["grad"] != sound["grad"]
