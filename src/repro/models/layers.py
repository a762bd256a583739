"""Common layers: norms, rotary, MLP, embedding, loss, and the pure-JAX
flash attention used for memory-bounded lowering on every backend.

All matmuls run through ``core.precision`` (bf16 operands, fp32 MXU
accumulation — paper §4.2) and layouts come from the ParallelPlan.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import manual, precision
from repro.core.layout import Layout, constrain

NEG = -1e30


# --------------------------------------------------------------------------
# norms / activations / rotary
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def act_fn(name: str):
    return jax.nn.gelu if name == "gelu" else jax.nn.silu


def rotary(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with D even; positions: (S,) or broadcastable."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs      # (S, half)
    cos = jnp.cos(angles)[..., None, :]                            # (S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# flash attention, pure JAX (double-scan online softmax)
# --------------------------------------------------------------------------

def flash_attention_jnp(
    q: jax.Array,                 # (B, Hq, S, D)
    k: jax.Array,                 # (B, Hkv, T, D)
    v: jax.Array,                 # (B, Hkv, T, Dv)
    *,
    causal: bool = True,
    window: Optional[Union[int, jax.Array]] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_offset: Union[int, jax.Array] = 0,
    bq: int = 512,
    bkv: int = 1024,
) -> jax.Array:
    """Memory-bounded attention: peak live = (B,Hq,bq,bkv) scores.

    Works under GSPMD with heads sharded (head-TP) and as the local body
    inside shard_map (SP).  ``window`` may be a traced array — gemma3's
    per-layer local/global switch inside one scanned stack.
    """
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    Dv = v.shape[-1]
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    bq = min(bq, S)
    bkv = min(bkv, T)
    # pad ragged sequence lengths up to the block size (padded kv columns
    # sit beyond the causal horizon of real queries; padded q rows are
    # sliced off the output)
    S_pad = (S + bq - 1) // bq * bq
    T_pad = (T + bkv - 1) // bkv * bkv
    if S_pad != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    if T_pad != T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    kv_valid, q_valid = T, S
    S, T = S_pad, T_pad
    nq, nk = S // bq, T // bkv

    qf = q.astype(jnp.float32) * scale
    qf = qf.reshape(B, Hkv, g, nq, bq, D)
    kc = jnp.moveaxis(k.reshape(B, Hkv, nk, bkv, D), 2, 0)   # (nk, B,Hkv,bkv,D)
    vc = jnp.moveaxis(v.reshape(B, Hkv, nk, bkv, Dv), 2, 0)

    kpos_base = jnp.arange(bkv)

    def q_block(args):
        qi, qb = args                                        # qb (B,Hkv,g,bq,D)
        qpos = q_offset + qi * bq + jnp.arange(bq)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, kb, vb = inp
            s = precision.einsum("bkgqd,bktd->bkgqt", qb, kb,
                                 policy=precision.FULL)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            kpos = kj * bkv + kpos_base
            mask = jnp.ones((bq, bkv), dtype=bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            if T != kv_valid:                     # kv padding columns
                mask &= (kpos < kv_valid)[None, :]
            s = jnp.where(mask, s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + precision.einsum(
                "bkgqt,bktd->bkgqd", p, vb, policy=precision.FULL)
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, g, bq, 1), NEG, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, bq, 1), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, bq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        return acc / jnp.where(l == 0.0, 1.0, l)

    out = jax.lax.map(q_block, (jnp.arange(nq), jnp.moveaxis(qf, 3, 0)))
    out = jnp.moveaxis(out, 0, 3).reshape(B, Hq, S, Dv)      # (B,Hq,S,Dv)
    if S != q_valid:
        out = out[:, :, :q_valid, :]
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,                 # (B, Hq, 1, D) one new token
    k: jax.Array,                 # (B, T, Hkv, D) cache (seq-major!)
    v: jax.Array,
    pos: jax.Array,               # scalar OR (B,): index of the new token
    *,
    window: Optional[Union[int, jax.Array]] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Flash-decoding layout: cache sharded on T; GSPMD reduces the softmax
    stats (tiny) and the output psum — see DESIGN §4.

    ``pos`` may be per-slot ``(B,)``: the serving engines decode ragged
    batches where every slot sits at its own position (no lockstep
    ``max(pos)`` — see ``serve/engine.py``)."""
    B, Hq, _, D = q.shape
    _, T, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, D) * scale
    s = precision.einsum("bkgd,btkd->bkgt", qf, k, policy=precision.FULL)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    kpos = jnp.arange(T)
    if pos.ndim == 0:
        mask = kpos <= pos
        if window is not None:
            mask &= kpos > pos - window
        s = jnp.where(mask[None, None, None, :], s, NEG)
    else:                          # per-slot positions: (B, T) mask
        mask = kpos[None, :] <= pos[:, None]
        if window is not None:
            mask &= kpos[None, :] > pos[:, None] - window
        s = jnp.where(mask[:, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = precision.einsum("bkgt,btkd->bkgd", p, v, policy=precision.FULL)
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


# --------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU) with TP layouts
# --------------------------------------------------------------------------

def decode_attention_ring(
    q: jax.Array,                 # (B, Hq, 1, D)
    k: jax.Array,                 # (B, W, Hkv, D) ring buffer
    v: jax.Array,
    pos: jax.Array,               # absolute position of the new token
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Sliding-window decode over a ring-buffer cache.

    Slot j holds absolute position  pos - ((pos - j) mod W)  (the last
    write to that slot); slots with negative absolute position (warmup)
    are masked.  Memory is O(W) instead of O(S) — gemma3's 5:1 local
    layers exist for exactly this.
    """
    B, Hq, _, D = q.shape
    _, W, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, D) * scale
    s = precision.einsum("bkgd,bwkd->bkgw", qf, k, policy=precision.FULL)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    j = jnp.arange(W)
    if pos.ndim == 0:
        abs_pos = pos - jnp.mod(pos - j, W)
        s = jnp.where((abs_pos >= 0)[None, None, None, :], s, NEG)
    else:                          # per-slot positions: (B, W) mask
        abs_pos = pos[:, None] - jnp.mod(pos[:, None] - j[None, :], W)
        s = jnp.where((abs_pos >= 0)[:, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = precision.einsum("bkgw,bwkd->bkgd", p, v, policy=precision.FULL)
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


def glu_mlp(x, w_gate, w_in, w_out, *, act="silu", policy,
            use_layouts=None, h_layout: Optional[Layout] = None,
            gather_layout: Optional[Layout] = None,
            out_layout: Optional[Layout] = None):
    """Gated MLP: col-parallel in, row-parallel out (the paper's
    model-parallel FC pair).

    ``h_layout`` pins the hidden activations to the TP axis so GSPMD
    realizes col->row parallel with a single reduce(-scatter) at the
    output.  ``gather_layout`` (sequence-parallel residuals) makes the
    seq->full all-gather explicit ON THE bf16 TENSOR — without it GSPMD
    gathers the fp32-converted operand of the dot: 2x wire (measured
    4.9 GiB/layer fp32 vs 2.5 bf16 on qwen2 train_4k; §Perf iter 1).
    """
    if gather_layout is not None:
        x = constrain(x, gather_layout)
    if use_layouts is not None:
        w_gate = constrain(w_gate, use_layouts["gate"])
        w_in = constrain(w_in, use_layouts["in"])
        w_out = constrain(w_out, use_layouts["out"])
    g = precision.einsum("bsd,df->bsf", x, w_gate, policy=policy)
    h = precision.einsum("bsd,df->bsf", x, w_in, policy=policy)
    if h_layout is not None:
        g = constrain(g.astype(policy.activation_dtype), h_layout)
        h = constrain(h.astype(policy.activation_dtype), h_layout)
    h = act_fn(act)(g.astype(jnp.float32)).astype(x.dtype) \
        * h.astype(x.dtype)
    out = precision.einsum("bsf,fd->bsd", h, w_out, policy=policy)
    if out_layout is not None:
        # pin the row-parallel output straight to its sharded layout so
        # GSPMD emits reduce-scatter instead of all-reduce + slice
        out = constrain(out, out_layout)
    return out.astype(x.dtype)


def glu_mlp_shardmap(x, w_gate, w_in, w_out, *, act, mesh, plan, policy):
    """Tensor-parallel gated MLP with EXPLICIT bf16 collectives.

    shard_map over the TP axis: all-gather the seq-sharded bf16 residual,
    col->row parallel locally, downcast, reduce-scatter back onto the
    sequence shards.  Exists because GSPMD + fp32-accumulating dots put
    the gathers/reductions on fp32 tensors (measured 2-4x wire on the
    head-TP archs; EXPERIMENTS §Perf iteration 5).  Backward is the exact
    transpose: RS(d_x) / AG(d_out), also bf16.
    """
    from jax.sharding import PartitionSpec as P
    tp = plan.tp_axis

    def body(xl, wg, wi, wo):
        xg = jax.lax.all_gather(xl, tp, axis=1, tiled=True)     # bf16 wire
        g = precision.einsum("bsd,df->bsf", xg, wg, policy=policy)
        h = precision.einsum("bsd,df->bsf", xg, wi, policy=policy)
        h = act_fn(act)(g) * h
        out = precision.einsum("bsf,fd->bsd", h.astype(xl.dtype), wo,
                               policy=policy)
        return jax.lax.psum_scatter(out.astype(xl.dtype), tp,
                                    scatter_dimension=1, tiled=True)

    return manual.shard_map(
        body, mesh=mesh,
        in_specs=(P(plan.batch_axes, tp, None), P(None, tp), P(None, tp),
                  P(tp, None)),
        out_specs=P(plan.batch_axes, tp, None),
    )(x, w_gate, w_in, w_out)


# --------------------------------------------------------------------------
# embedding / unembedding / loss
# --------------------------------------------------------------------------

def embed(tokens: jax.Array, table: jax.Array, *, scale: bool,
          out_layout: Optional[Layout] = None) -> jax.Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(table.shape[-1] ** 0.5, x.dtype)
    if out_layout is not None:
        x = constrain(x, out_layout)
    return x


def embed_shard_map(tokens: jax.Array, table: jax.Array, mesh, *,
                    batch_axes, tp_axis: str, scale: bool) -> jax.Array:
    """Embedding gather as an explicit shard_map: each model shard holds the
    (V, D/tp) column block and does a comm-free local take.

    Exists because the GSPMD partitioner mis-partitions gather-from-a-
    D-sharded-table inside a scanned (microbatched) train step — the same
    class of layout decision dMath §3.2 makes explicitly rather than
    leaving to inference.  Backward (scatter-add into the table shard +
    psum over the batch axes) falls out of shard_map autodiff.
    """
    from jax.sharding import PartitionSpec as P
    d_full = table.shape[-1]
    mult = jnp.asarray(d_full ** 0.5, table.dtype) if scale else None

    def body(tok, tab):
        e = jnp.take(tab, tok, axis=0)
        return e * mult if mult is not None else e

    return manual.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None), P(None, tp_axis)),
        out_specs=P(batch_axes, None, tp_axis),
    )(tokens, table)


def unembed(x: jax.Array, w: jax.Array, *, policy,
            out_layout: Optional[Layout] = None) -> jax.Array:
    logits = precision.einsum("bsd,dv->bsv", x, w, policy=policy)
    if out_layout is not None:
        logits = constrain(logits, out_layout)
    return logits


def lm_loss(logits: jax.Array, labels: jax.Array, *, vocab_real: int
            ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over a vocab-sharded logits tensor.

    The gold logit is extracted with an iota==label masked reduction (local
    on each vocab shard + a cheap psum) instead of take_along_axis, so no
    gather communication and no (B,S,V) one-hot is materialized.  Vocab
    padding columns are masked to -inf.  Labels < 0 are ignored.
    """
    B, S, V = logits.shape
    lf = logits.astype(jnp.float32)
    vio = jax.lax.broadcasted_iota(jnp.int32, (1, 1, V), 2)
    lf = jnp.where(vio >= vocab_real, NEG, lf)
    logz = jax.nn.logsumexp(lf, axis=-1)                       # (B, S)
    gold = jnp.sum(jnp.where(vio == labels[..., None], lf, 0.0), axis=-1)
    valid = (labels >= 0).astype(jnp.float32)
    nll = (logz - gold) * valid
    denom = jnp.maximum(jnp.sum(valid), 1.0)
    return jnp.sum(nll) / denom, denom
