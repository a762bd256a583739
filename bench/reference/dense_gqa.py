"""Plain float32 reference of the dense GQA decoder (Qwen2, Qwen3).

Follows the published description: token embedding; per layer an RMSNorm,
grouped-query attention (QKV bias for Qwen2, per-head RMSNorm of q and k
before the rotary embedding for Qwen3, half-split rotary embedding,
causal softmax), a residual add, an RMSNorm, a SwiGLU MLP and a residual
add; a final RMSNorm and the output head, the transposed embedding where
the configuration ties them.

Everything is float32 with every matmul at ``Precision.HIGHEST``; there
are no kernels, no cache and no batching.  To fit at published widths it
works one sequence at a time, layer by layer (a scan over the stacked
layers), with attention in blocks of queries and the head in blocks of
rows or of the vocabulary.  Weights come in as the benchmark's tree
(``bench/weights.py``) and are upcast where they are used.

``cast`` is applied to both operands of every linear layer (projections,
MLP and head) and is the identity for the reference.  The control passes
:func:`fp8`, which rounds each operand to float8 e4m3 with a per-tensor
scale: the reference computed in the next precision below bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.model_config import Shape

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512          # queries per attention block
ROW_BLOCK = 512        # rows per head block


def ident(x):
    return x


def fp8(x):
    """Round to float8 e4m3 under a per-tensor scale (straight through)."""
    x = x.astype(F32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


CASTS = {"f32": ident, "fp8": fp8}


def _mm(eq, a, b, cast):
    return jnp.einsum(eq, cast(a.astype(F32)), cast(b.astype(F32)),
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, pos, theta):
    """Half-split rotary embedding of x (S, H, hd) at positions (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv                    # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal GQA softmax attention, f32, in blocks of queries.
    q (S, H, hd); k, v (S, Hkv, hd) -> (S, H, hd)."""
    S, H, hd = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    nb = -(-S // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0)))
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / hd ** 0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(nb))
    return out.reshape(nb * Q_BLOCK, H, hd)[:S]


def layer(x, lp, s: Shape, pos, cast):
    """One decoder layer on x (S, D) f32."""
    h = rms_norm(x, lp["ln1"], s.norm_eps)
    a = lp["attn"]
    q = _mm("sd,dhk->shk", h, a["wq"], cast)
    k = _mm("sd,dhk->shk", h, a["wk"], cast)
    v = _mm("sd,dhk->shk", h, a["wv"], cast)
    if s.qkv_bias:
        q, k, v = q + a["bq"].astype(F32), k + a["bk"].astype(F32), \
            v + a["bv"].astype(F32)
    if s.qk_norm:
        q = rms_norm(q, a["q_norm"], s.norm_eps)
        k = rms_norm(k, a["k_norm"], s.norm_eps)
    q, k = rope(q, pos, s.rope_theta), rope(k, pos, s.rope_theta)
    x = x + _mm("shk,hkd->sd", attention(q, k, v), a["wo"], cast)
    h = rms_norm(x, lp["ln2"], s.norm_eps)
    m = lp["mlp"]
    u = jax.nn.silu(_mm("sd,df->sf", h, m["gate"], cast)) \
        * _mm("sd,df->sf", h, m["in"], cast)
    return x + _mm("sf,fd->sd", u, m["out"], cast)


def hidden(w, tokens, s: Shape, cast=ident):
    """Final-normed hidden states (S, D) of one sequence of tokens (S,)."""
    pos = jnp.arange(tokens.shape[0])
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)

    def body(x, lp):
        return layer(x, lp, s, pos, cast), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, w["layers"])
    return rms_norm(x, w["final_norm"], s.norm_eps)


def head_weight(w, s: Shape):
    return w["embed"].T if s.tied else w["unembed"]


def logits(w, h, s: Shape, cast=ident):
    """Logits (n, V) of hidden rows h (n, D), in blocks of rows."""
    W = head_weight(w, s)
    n = h.shape[0]
    nb = -(-n // ROW_BLOCK)
    hp = jnp.pad(h, ((0, nb * ROW_BLOCK - n), (0, 0)))

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(hp, i * ROW_BLOCK, ROW_BLOCK)
        return _mm("nd,dv->nv", hb, W, cast)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(nb))
    return out.reshape(nb * ROW_BLOCK, -1)[:n]


def seq_loss(w, tokens, labels, s: Shape, cast=ident):
    """Mean next-token cross-entropy of one sequence."""
    h = hidden(w, tokens, s, cast)
    W = head_weight(w, s)
    S = tokens.shape[0]
    nb = -(-S // ROW_BLOCK)
    hp = jnp.pad(h, ((0, nb * ROW_BLOCK - S), (0, 0)))
    lp = jnp.pad(labels, (0, nb * ROW_BLOCK - S), constant_values=-1)

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(hp, i * ROW_BLOCK, ROW_BLOCK)
        yb = jax.lax.dynamic_slice_in_dim(lp, i * ROW_BLOCK, ROW_BLOCK)
        z = _mm("nd,dv->nv", hb, W, cast)
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, jnp.maximum(yb, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(yb >= 0, nll, 0.0))

    return jnp.sum(jax.lax.map(jax.checkpoint(block), jnp.arange(nb))) / S


@functools.partial(jax.jit, static_argnames=("s", "cast"))
def row_logits(w, tokens, rows, s: Shape, cast=ident):
    """Logits (R, V) that the sequence ``tokens`` (S,) gives at positions
    ``rows`` (R,), each predicting the token after it.  Positions after
    the last one asked for do not change them (attention is causal), so
    callers pad ``tokens`` to a few fixed lengths."""
    return logits(w, hidden(w, tokens, s, cast)[rows], s, cast)


@functools.partial(jax.jit, static_argnames=("s", "cast"))
def loss_and_grad(w, tokens, labels, s: Shape, cast=ident):
    """Mean cross-entropy of one sequence and its gradient."""
    return jax.value_and_grad(seq_loss)(w, tokens, labels, s, cast)
