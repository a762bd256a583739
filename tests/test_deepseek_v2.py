"""DeepSeek-V2 on the serve path: latent attention over the latent paged
pool, YaRN rotary, the leading dense layer and the held experts'
dropless MoE, each against the plain float32 reference of
``bench/reference/deepseek_v2.py`` at a tiny size on the CPU."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import model_config  # noqa: E402
from bench.reference import deepseek_v2 as ref  # noqa: E402
from bench.reference import deepseek_v2_weights as ref_weights  # noqa: E402
from bench.tests import tiny_mla  # noqa: E402
from repro import obs as obs_mod  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import precision  # noqa: E402
from repro.kernels import paged_attention  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import Model, mla, moe  # noqa: E402
from repro.serve import (ContinuousEngine, Request,  # noqa: E402
                         kv_bytes_per_block, pool_pages_for_budget)

#: bfloat16 weights, activations and latent rows against the float32
#: reference move the logits by 2.1-2.5% of their spread at this size; a
#: mistake of the mechanism moves them by 40% or more (a missing mscale^2
#: in the softmax scale 41-55%, k_pe roped one position off 41-77%, the
#: decode kernel reading another layer's rows 115%, writes to another
#: layer's rows 126-135%)
LOGIT_TOL = 0.05


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


@pytest.fixture(scope="module")
def tiny(mesh):
    """(model, params, reference shape) of the tiny config, held share 1
    of 4, with q and the keys scaled x8 so that attention is sharp."""
    cfg, s = tiny_mla.config_pair()
    model = Model(cfg, mesh)
    w = ref_weights.make(s, 11)
    ref_weights.check_against(w, model.param_sds())

    def sharpen(stack):
        a = dict(stack["attn"])
        a["wq"], a["wk_b"] = a["wq"] * 8, a["wk_b"] * 8
        a["wkv_a"] = a["wkv_a"].at[..., s.kv_rank:].multiply(8)
        return dict(stack, attn=a)

    w = dict(w, layers=sharpen(w["layers"]),
             dense_layers=sharpen(w["dense_layers"]))
    return model, w, s


def _reference_logits(w, toks, s):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.row_logits(
            w, jnp.asarray(toks, jnp.int32), jnp.arange(len(toks)), s))


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def test_yarn_inv_freq_and_softmax_scale_follow_the_formula():
    """DeepSeek-V2-Lite's rope_scaling (factor 40, original 4096,
    beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707): the
    correction range is dims 10-23 of 32 (floor and ceil of
    64 ln(4096 / (2 pi beta)) / (2 ln 10^4)); below it the frequency
    is extrapolated, above it interpolated by 40, linearly blended
    between.  Program and reference agree with that to float32 rounding
    (1e-6 relative)."""
    cfg = get_config("deepseek-v2-lite")
    conf = model_config.load(os.path.join(
        REPO, "bench", "configs", "deepseek-v2-lite-ep8.json"))
    s = ref.shape_of(conf)
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    low, high = 10, 23
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(mla.rope_inv_freq(cfg), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.inv_freq(s)), want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert ref.softmax_scale(s) == pytest.approx(192 ** -0.5 * m * m)
    assert mla.rope_mscale(cfg) == 1.0


# ---------------------------------------------------------------------------
# the latent kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,dr,page,layer", [(32, 8, 16, 1), (64, 64, 8, 0)])
def test_latent_kernel_matches_oracle(r, dr, page, layer):
    """Interpret-mode kernel against the gather oracle, pages out of order
    and partial last pages, and the oracle against attention over the
    rows written out in position order.  The kernel feeds bfloat16 query
    and probabilities to the matmuls: 2% of the output's largest value."""
    rng = np.random.default_rng(r + layer)
    L, P, H, B, n = 2, 13, 4, 3, 4
    pack = min(paged_attention.rope_pack(dr), page)
    c = jnp.asarray(rng.normal(size=(L, P, page, r)), jnp.bfloat16)
    pe = jnp.asarray(rng.normal(size=(L, P, page // pack, pack * dr)),
                     jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, H, r + dr)), jnp.float32)
    table = rng.permutation(np.arange(1, P))[:B * n].reshape(B, n)
    lens = np.array([1, page + 5, page * n - 3], np.int32)
    args = (q, c, pe, jnp.asarray(table, jnp.int32), jnp.asarray(lens),
            jnp.int32(layer))
    got = paged_attention.paged_decode_latent_attention(
        *args, scale=0.3, interpret=True)
    want = kref.paged_decode_latent_attention(*args, scale=0.3)
    assert np.abs(np.asarray(got - want)).max() <= 0.02 * np.abs(
        np.asarray(want)).max()
    # the oracle: rows unpacked by hand, in position order
    for b in range(B):
        rows = []
        for j in range(-(-int(lens[b]) // page)):
            blk = np.asarray(pe[layer, table[b, j]], np.float32)
            for t in range(page):
                k, i = divmod(t, page // pack)
                rows.append(np.concatenate(
                    [np.asarray(c[layer, table[b, j], t], np.float32),
                     blk[i, k * dr:(k + 1) * dr]]))
        kv = np.stack(rows)[:lens[b]]
        sc = 0.3 * np.asarray(q[b]) @ kv.T
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(want[b]), p @ kv[:, :r],
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_mla_prefill_matches_reference(mesh, tiny):
    """One prefill chunk in the decompressed form gives the reference's
    logits at every position."""
    model, w, s = tiny
    toks = np.random.default_rng(2).integers(0, s.vocab, 40)
    with jax.set_mesh(mesh):
        cache = model.init_paged_cache(1, 64, page_size=16)
        logits, _ = jax.jit(model.prefill_chunk_paged)(
            w, cache, jnp.asarray(toks[None], jnp.int32), cache["table"][0],
            jnp.int32(0))
    want = _reference_logits(w, toks, s)
    got = np.asarray(logits[0], np.float32)
    assert np.abs(got - want).max() <= LOGIT_TOL * want.std()


def test_paged_prefill_then_decode_matches_reference(mesh, tiny):
    """Chunked prefill, then greedy decode in the absorbed form through a
    permuted page table, beside an idle slot, gives the reference's full
    forward logits at every served position."""
    model, w, s = tiny
    page, chunk, n_prompt, n_new = 16, 16, 37, 5
    prompt = np.random.default_rng(3).integers(0, s.vocab, n_prompt)
    table = np.array([[9, 4, 11, 2], [0, 0, 0, 0]], np.int32)
    with jax.set_mesh(mesh):
        cache = dict(model.init_paged_pool(12, page),
                     table=jnp.asarray(table))
        pre = jax.jit(model.prefill_chunk_paged)
        dec = jax.jit(model.decode_step_paged)
        for start in range(0, n_prompt, chunk):
            c = np.zeros((1, chunk), np.int32)
            n = min(chunk, n_prompt - start)
            c[0, :n] = prompt[start:start + n]
            logits, cache = pre(w, cache, jnp.asarray(c),
                                jnp.asarray(table[0]), jnp.int32(start))
        rows = [np.asarray(logits[0, n - 1], np.float32)]
        toks = list(prompt)
        for i in range(n_new):
            toks.append(int(np.argmax(rows[-1])))
            logits, cache = dec(
                w, cache, jnp.asarray([[toks[-1]], [0]], jnp.int32),
                jnp.asarray([n_prompt + i, 0], jnp.int32))
            rows.append(np.asarray(logits[0, 0], np.float32))
    want = _reference_logits(w, toks, s)[n_prompt - 1:]
    assert np.abs(np.stack(rows) - want).max() <= LOGIT_TOL * want.std()
    held = cache["held_tokens"]
    assert held.shape == (s.moe_layers, s.held[1]) and held.dtype == jnp.int32
    # the decode steps' two rows each route top_k tokens per MoE layer
    assert 0 < int(held.sum(1).max()) <= s.top_k * 2 * n_new


# ---------------------------------------------------------------------------
# the held experts' dropless layer
# ---------------------------------------------------------------------------

def _moe_case(E, K, shard, norm_topk, T=24, seed=0):
    """(program cfg, reference shape, f32 weights of the held share, x
    (1, T, D)) of one tiny MoE layer."""
    c = dict(tiny_mla.TINY_MLA, num_experts_per_tok=K,
             norm_topk_prob=norm_topk, n_routed_experts=E // shard[1],
             published={"n_routed_experts": E},
             program=dict(tiny_mla.TINY_MLA["program"], n_experts=E,
                          top_k=K, expert_shard=list(shard)))
    cfg = dataclasses.replace(tiny_mla.program_config(c),
                              norm_topk_prob=norm_topk)
    s = ref.shape_of(c)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, T, s.d_model))
    return cfg, s, _layer_weights(s, seed), x


def _layer_weights(s, seed):
    w = ref_weights.make(s, seed, dtype=jnp.float32)["layers"]["moe"]
    return jax.tree.map(lambda a: a[0] * 8, w)


def _forward(cfg, w, x):
    return moe.forward_held(x, w, cfg, policy=precision.FULL)


@pytest.mark.parametrize("norm_topk", [False, True])
def test_topk_weights_renormalised_only_when_asked(norm_topk):
    """With every expert held, the layer is the softmax gate's top-k sum
    of experts plus the shared experts; the weights are the raw
    probabilities unless ``norm_topk_prob``."""
    cfg, s, w, x = _moe_case(E=8, K=3, shard=(0, 1), norm_topk=norm_topk)
    y, counts = _forward(cfg, w, x)
    t = np.asarray(x[0], np.float64)
    probs = jax.nn.softmax(jnp.asarray(t @ np.asarray(w["router"])), -1)
    probs = np.asarray(probs, np.float64)
    silu = lambda v: v / (1 + np.exp(-v))

    def ffn(v, g, i, o):
        return (silu(v @ g) * (v @ i)) @ o

    want = ffn(t, *(np.asarray(w[k], np.float64) for k in
                    ("shared_gate", "shared_in", "shared_out")))
    for n in range(t.shape[0]):
        top = np.argsort(-probs[n])[:3]
        gate = probs[n, top] / (probs[n, top].sum() if norm_topk else 1.0)
        for e, g in zip(top, gate):
            want[n] += g * ffn(t[n], *(np.asarray(w[k][e], np.float64)
                                       for k in ("w_gate", "w_in", "w_out")))
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())
    assert int(counts.sum()) == 3 * t.shape[0]


def test_dropless_when_every_token_routes_to_one_held_expert():
    """A router that sends every token to held expert 2 first: all T
    tokens reach it (a capacity buffer of T*K/E rows would drop most of
    them) and the output matches the reference layer."""
    cfg, s, w, x = _moe_case(E=8, K=2, shard=(0, 2), norm_topk=False, T=64)
    x = x.at[..., 0].set(10.0)
    w = dict(w, router=w["router"].at[0, 2].set(5.0))
    y, counts = _forward(cfg, w, x)
    assert counts.tolist()[2] == 64
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x[0], w, s, ref.ident)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4 * float(
                                   jnp.abs(want).max()))


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of a 16-expert layer (2 held each), with the
    shared experts that every chip computes counted once, add up to the
    reference layer that holds all 16."""
    E, n = 16, 8
    ys = []
    for i in range(n):
        cfg, s, _, x = _moe_case(E=E, K=3, shard=(i, n), norm_topk=False)
        if i == 0:
            s_all = dataclasses.replace(s, held=(0, E))
            full = _layer_weights(s_all, 0)
        own = dict(full, **{k: full[k][2 * i:2 * i + 2]
                            for k in ("w_gate", "w_in", "w_out")})
        ys.append(_forward(cfg, own, x)[0][0])
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(x[0], full["shared_gate"], full["shared_in"],
                            full["shared_out"], ref.ident)
        want = ref.moe(x[0], full, s_all, ref.ident)
    got = sum(ys) - (n - 1) * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4 * float(
                                   jnp.abs(want).max()))


# ---------------------------------------------------------------------------
# the latent pool and the engine
# ---------------------------------------------------------------------------

def test_latent_pool_of_the_cell_is_one_row_per_token_per_layer(mesh):
    """32 slots of 8192 positions over 27 layers at one 1152-byte latent
    row per token and layer, in the block manager's reckoning and in the
    arrays the model allocates (plus the NULL page)."""
    conf = model_config.load(os.path.join(
        REPO, "bench", "configs", "deepseek-v2-lite-ep8.json"))
    cfg = model_config.program_config(conf)
    page, slots, seq = 256, 32, 8192
    want = slots * seq * 27 * 1152
    assert kv_bytes_per_block(cfg, page) * (slots * seq // page) == want
    assert pool_pages_for_budget(want, cfg, page) == slots * seq // page
    with jax.set_mesh(mesh):
        pool = jax.eval_shape(lambda: Model(cfg, mesh).init_paged_pool(
            1 + slots * seq // page, page))
    got = sum(pool[n].size * pool[n].dtype.itemsize
              for n in ("latent_pages", "rope_pages"))
    assert got == want + kv_bytes_per_block(cfg, page)
    assert pool["held_tokens"].shape == (26, 8)


def test_engine_counts_tokens_routed_to_each_held_expert(mesh, tiny):
    """The continuous engine, with telemetry off, serves the tiny model;
    its decode steps count the tokens routed to each held expert on the
    device, and ``held_tokens`` reads the count back: (MoE layers, held
    experts), at most top_k per slot and step, and growing with steps."""
    model, w, s = tiny
    rng = np.random.default_rng(5)
    with jax.set_mesh(mesh):
        eng = ContinuousEngine(model, w, batch_slots=2, max_seq=64,
                               page_size=16, prefill_chunk=16)
        assert eng.obs is obs_mod.NULL
        assert not eng.held_tokens().any()
        for i in range(3):
            eng.submit(Request(rid=i, prompt=rng.integers(0, s.vocab, 20),
                               max_new_tokens=6))
        while not eng.held_tokens().any():
            eng.step()
        first = eng.held_tokens()
        fin = eng.run()
    assert len(fin) == 3
    held = eng.held_tokens()
    assert held.shape == (s.moe_layers, s.held[1])
    assert 0 < held.sum(1).max() <= s.top_k * 2 * eng._tick
    assert (held >= first).all() and held.sum() > first.sum()
