"""Plain float32 reference of DeepSeek-V2 (MLA, DeepSeekMoE) on one chip's
share of its routed experts.

Follows HF's ``modeling_deepseek`` (DeepSeek-V2-Lite) equations: token
embedding; per layer an RMSNorm, multi-head latent attention in the
decompressed form (q from ``wq``; ``[c_kv | k_pe]`` from ``wkv_a``, c_kv
RMS-normed; every head's key ``[c_kv W_uk | k_pe]`` and value
``c_kv W_uv``; YaRN rotary on q_pe and k_pe; causal softmax at
``softmax_scale = (qk_nope + qk_rope)^-0.5 * mscale(factor,
mscale_all_dim)^2``), a residual add, an RMSNorm, then the first
``first_k_dense`` layers a SwiGLU MLP of width ``intermediate_size`` and
the rest an MoE (softmax gate over all routed experts, greedy top-k,
weights renormalised only with ``norm_topk_prob``, times
``routed_scaling_factor``, plus the shared experts' SwiGLU), a residual
add; a final RMSNorm and the untied head.

Departures, each shared with the program:

- only the held experts' part of the routed sum is computed (the chip's
  share: :attr:`MLAShape.held`); the other experts' part is left out;
- the rope halves are split, not interleaved: HF permutes the
  interleaved columns into halves before rotating, and on random weights
  that permutation is a relabelling of columns;
- YaRN's inverse frequencies are computed in float32 (HF: torch float32).

Everything is float32 with every matmul at ``Precision.HIGHEST``; no
kernels, no cache, no batching.  One sequence at a time, layer by layer
(a scan over each stack), attention in blocks of queries, the held
experts one at a time and the head in blocks of rows, so that it fits at
published widths.  ``cast`` is applied to both operands of every linear
layer (projections, router, experts, MLP and head): the identity for the
reference, :func:`bench.reference.dense_gqa.fp8` for the control.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.dense_gqa import F32, HIGHEST, _mm, fp8, ident, rms_norm

Q_BLOCK = 512          # queries per attention block
ROW_BLOCK = 512        # rows per head block

__all__ = ["MLAShape", "shape_of", "row_logits", "hidden", "fp8", "ident"]


@dataclasses.dataclass(frozen=True)
class MLAShape:
    """The published keys the reference, the weights and the yardsticks
    read, and this chip's share of the routed experts."""

    name: str
    layers: int
    first_dense: int
    d_model: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    d_expert: int
    experts: int               # routed experts the router scores
    held: tuple                # (first, count): the experts held here
    top_k: int
    shared: int
    norm_topk: bool
    routed_scale: float
    vocab: int
    rope_theta: float
    yarn: tuple                # (factor, original_max, beta_fast,
                               #  beta_slow, mscale, mscale_all_dim)
    norm_eps: float

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense

    @property
    def latent_width(self) -> int:
        return self.kv_rank + self.rope

    @property
    def latent_bytes_per_token(self) -> int:
        """bf16 latent row of one position over all layers."""
        return self.layers * self.latent_width * 2

    @property
    def attn_params(self) -> int:
        D, H = self.d_model, self.heads
        return (D * H * (self.nope + self.rope) + D * self.latent_width
                + self.kv_rank * H * (self.nope + self.v_dim)
                + H * self.v_dim * D)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_expert


def shape_of(conf: dict) -> MLAShape:
    """The MLA shape of a configuration file: the published keys as run
    (``n_routed_experts`` is the count held here), the routed count the
    router scores from ``published``, and the held share from the
    program's ``expert_shard``."""
    rs = conf["rope_scaling"]
    held_n = conf["n_routed_experts"]
    routed = conf.get("published", {}).get("n_routed_experts", held_n)
    shard = conf.get("program", {}).get("expert_shard", [0, 1])
    return MLAShape(
        name=conf["name"], layers=conf["num_hidden_layers"],
        first_dense=conf["first_k_dense_replace"],
        d_model=conf["hidden_size"], heads=conf["num_attention_heads"],
        kv_rank=conf["kv_lora_rank"], nope=conf["qk_nope_head_dim"],
        rope=conf["qk_rope_head_dim"], v_dim=conf["v_head_dim"],
        d_ff=conf["intermediate_size"],
        d_expert=conf["moe_intermediate_size"], experts=routed,
        held=(shard[0] * held_n, held_n), top_k=conf["num_experts_per_tok"],
        shared=conf["n_shared_experts"],
        norm_topk=bool(conf["norm_topk_prob"]),
        routed_scale=float(conf["routed_scaling_factor"]),
        vocab=conf["vocab_size"], rope_theta=float(conf["rope_theta"]),
        yarn=(float(rs["factor"]),
              int(rs["original_max_position_embeddings"]),
              float(rs["beta_fast"]), float(rs["beta_slow"]),
              float(rs["mscale"]), float(rs["mscale_all_dim"])),
        norm_eps=float(conf["rms_norm_eps"]))


# --------------------------------------------------------------------------
# YaRN (HF DeepseekV2YarnRotaryEmbedding)
# --------------------------------------------------------------------------

def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(s: MLAShape) -> jax.Array:
    factor, orig, beta_fast, beta_slow, _, _ = s.yarn
    dim, base = s.rope, s.rope_theta
    freq_extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    freq_inter = 1.0 / (factor * base ** (jnp.arange(0, dim, 2, dtype=F32)
                                          / dim))

    def find_dim(n_rot):
        return (dim * math.log(orig / (n_rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def softmax_scale(s: MLAShape) -> float:
    factor, _, _, _, _, mscale_all_dim = s.yarn
    m = yarn_get_mscale(factor, mscale_all_dim)
    return (s.nope + s.rope) ** -0.5 * m * m


def rope(x, pos, s: MLAShape):
    """Rotary of x (S, H, rope) at positions (S,), halves split."""
    factor, _, _, _, mscale, mscale_all_dim = s.yarn
    m = yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
                                                          mscale_all_dim)
    ang = pos.astype(F32)[:, None] * inv_freq(s)[None, :]
    emb = jnp.concatenate([ang, ang], -1)                  # (S, rope)
    cos, sin = (jnp.cos(emb) * m)[:, None, :], (jnp.sin(emb) * m)[:, None, :]
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def attention(q, k, v, scale):
    """Causal softmax attention in blocks of queries.
    q, k (S, H, dq); v (S, H, dv) -> (S, H, dv)."""
    S = q.shape[0]
    nb = -(-S // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0)))
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(nb))
    return out.reshape(nb * Q_BLOCK, *out.shape[2:])[:S]


def mla(h, a, s: MLAShape, pos, cast):
    """Latent attention, decompressed, on normed hidden h (S, D)."""
    r = s.kv_rank
    q = _mm("sd,dhk->shk", h, a["wq"], cast)
    q_nope, q_pe = q[..., :s.nope], q[..., s.nope:]
    kv = _mm("sd,dr->sr", h, a["wkv_a"], cast)
    c = rms_norm(kv[:, :r], a["kv_norm"], s.norm_eps)
    k_pe = rope(kv[:, None, r:], pos, s)                     # (S, 1, rope)
    q_pe = rope(q_pe, pos, s)
    k_nope = _mm("sr,rhk->shk", c, a["wk_b"], cast)
    v = _mm("sr,rhk->shk", c, a["wv_b"], cast)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:2] + (s.rope,))], -1)
    qq = jnp.concatenate([q_nope, q_pe], -1)
    out = attention(qq, k, v, softmax_scale(s))
    return _mm("shk,hkd->sd", out, a["wo"], cast)


def swiglu(h, gate, w_in, w_out, cast):
    u = jax.nn.silu(_mm("sd,df->sf", h, gate, cast)) \
        * _mm("sd,df->sf", h, w_in, cast)
    return _mm("sf,fd->sd", u, w_out, cast)


def moe(h, m, s: MLAShape, cast):
    """The held experts' part of the routed sum, and the shared experts."""
    probs = jax.nn.softmax(_mm("sd,de->se", h, m["router"], cast), axis=-1)
    vals, idx = jax.lax.top_k(probs, s.top_k)               # (S, K)
    if s.norm_topk:
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    vals = vals * s.routed_scale
    first, n = s.held

    def expert(y, e):
        w = jnp.sum(jnp.where(idx == first + e, vals, 0.0), -1)  # (S,)
        f = swiglu(h, m["w_gate"][e], m["w_in"][e], m["w_out"][e], cast)
        return y + w[:, None] * f, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(n))
    return y + swiglu(h, m["shared_gate"], m["shared_in"], m["shared_out"],
                      cast)


def hidden(w, tokens, s: MLAShape, cast=ident):
    """Final-normed hidden states (S, D) of one sequence of tokens (S,)."""
    pos = jnp.arange(tokens.shape[0])
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)

    def dense_layer(x, lp):
        x = x + mla(rms_norm(x, lp["ln1"], s.norm_eps), lp["attn"], s, pos,
                    cast)
        m = lp["mlp"]
        return x + swiglu(rms_norm(x, lp["ln2"], s.norm_eps), m["gate"],
                          m["in"], m["out"], cast), None

    def moe_layer(x, lp):
        x = x + mla(rms_norm(x, lp["ln1"], s.norm_eps), lp["attn"], s, pos,
                    cast)
        return x + moe(rms_norm(x, lp["ln2"], s.norm_eps), lp["moe"], s,
                       cast), None

    if s.first_dense:
        x, _ = jax.lax.scan(dense_layer, x, w["dense_layers"])
    x, _ = jax.lax.scan(moe_layer, x, w["layers"])
    return rms_norm(x, w["final_norm"], s.norm_eps)


def logits(w, h, cast=ident):
    """Logits (n, V) of hidden rows h (n, D), in blocks of rows."""
    n = h.shape[0]
    nb = -(-n // ROW_BLOCK)
    hp = jnp.pad(h, ((0, nb * ROW_BLOCK - n), (0, 0)))

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(hp, i * ROW_BLOCK, ROW_BLOCK)
        return _mm("nd,dv->nv", hb, w["unembed"], cast)

    out = jax.lax.map(block, jnp.arange(nb))
    return out.reshape(nb * ROW_BLOCK, -1)[:n]


@functools.partial(jax.jit, static_argnames=("s", "cast"))
def row_logits(w, tokens, rows, s: MLAShape, cast=ident):
    """Logits (R, V) that the sequence ``tokens`` (S,) gives at positions
    ``rows`` (R,), each predicting the token after it (attention is
    causal, so callers may pad ``tokens``)."""
    return logits(w, hidden(w, tokens, s, cast)[rows], cast)
