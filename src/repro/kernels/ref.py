"""Pure-jnp oracles for every Pallas kernel.

Each function is the bit-faithful *semantic* definition the kernels are
tested against (fp32 math throughout so the oracle itself has no rounding
surprises).  They are also the production fallback on backends without
Mosaic (this CPU container runs them; TPU runs the kernels).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# GEMM (mixed precision: narrow storage, fp32 accumulate — paper §4.2)
# --------------------------------------------------------------------------

def matmul(a: jax.Array, b: jax.Array, out_dtype=None) -> jax.Array:
    """C = A @ B with fp32 accumulation regardless of storage dtype."""
    out_dtype = out_dtype or a.dtype
    c = jnp.matmul(a, b, preferred_element_type=jnp.float32)
    return c.astype(out_dtype)


def matmul_dequant(a: jax.Array, b_q: jax.Array, b_scale: jax.Array,
                   out_dtype=None) -> jax.Array:
    """C = (A @ B_q) * scale — the unfused composition: widen the int8
    weights to the activation dtype (exact), matmul, scale the fp32 result
    per column.  Per-column scales commute with the k-sum, so this defines
    the fused epilogue's semantics."""
    out_dtype = out_dtype or a.dtype
    c = jnp.matmul(a, b_q.astype(a.dtype),
                   preferred_element_type=jnp.float32)
    return (c * b_scale.astype(jnp.float32)[None, :]).astype(out_dtype)


# --------------------------------------------------------------------------
# Quantize-compress (the int8 wire format of comms/compressed.py)
# --------------------------------------------------------------------------

def quantize_compress(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(q int8, scale fp32 scalar) with comms/compressed.py's exact affine
    format: scale = absmax/127 + 1e-12, q = clip(round(x/scale), +-127)."""
    v = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(v))
    scale = absmax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_int8(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Round/clip/cast against a precomputed (group-agreed) scale."""
    v = x.astype(jnp.float32)
    return jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)


def quantize_int8_per_channel(w: jax.Array
                              ) -> Tuple[jax.Array, jax.Array]:
    """Per-output-column int8 weights for the dequant-fused GEMM:
    (q (K,N) int8, scale (N,) fp32)."""
    v = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(v), axis=0)
    scale = absmax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(v / scale[None, :]), -127, 127).astype(jnp.int8)
    return q, scale


# --------------------------------------------------------------------------
# Attention (GQA + causal + sliding window + logit softcap)
# --------------------------------------------------------------------------

def attention(
    q: jax.Array,               # (B, Hq, S, D)
    k: jax.Array,               # (B, Hkv, T, D)
    v: jax.Array,               # (B, Hkv, T, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,     # sliding window size (gemma3 local)
    softcap: Optional[float] = None,  # logit soft-capping (gemma)
    scale: Optional[float] = None,
    q_offset: int = 0,          # absolute position of q[0] (decode: T - Sq)
) -> jax.Array:
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (D ** 0.5)

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # expand kv heads to q heads
    kf = jnp.repeat(kf, g, axis=1)
    vf = jnp.repeat(vf, g, axis=1)

    scores = jnp.einsum("bhsd,bhtd->bhst", qf, kf)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)

    qpos = jnp.arange(S) + q_offset
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None], scores, -jnp.inf)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vf)
    return out.astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,             # (B, Hq, hd)   one query token per sequence
    k_pages: jax.Array,       # (L, P, page, Hkv*hd) the stacked pool
    v_pages: jax.Array,       # (L, P, page, Hkv*hd)
    block_table: jax.Array,   # (B, n_pages) int32
    seq_lens: jax.Array,      # (B,) int32 — live length (pos + 1)
    layer: jax.Array,         # int32 scalar — the layer of the pool to read
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Gather-then-attend definition of the paged decode kernel.

    Logical page j of sequence b is physical page ``block_table[b, j]`` of
    layer ``layer``; gathering rebuilds the dense (B, T, Hkv, hd) cache
    from those rows alone, then the math is
    ``models/layers.decode_attention`` with the mask ``t < seq_lens[b]``.
    """
    B, Hq, hd = q.shape
    page, width = k_pages.shape[2:]
    Hkv = width // hd
    n_pages = block_table.shape[1]
    g = Hq // Hkv
    T = n_pages * page
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)

    kf = k_pages[layer, block_table].reshape(B, T, Hkv, hd)
    vf = v_pages[layer, block_table].reshape(B, T, Hkv, hd)
    kf, vf = kf.astype(jnp.float32), vf.astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, hd) * scale

    s = jnp.einsum("bkgd,btkd->bkgt", qf, kf)            # (B,Hkv,g,T)
    mask = jnp.arange(T)[None, :] < seq_lens[:, None]    # (B,T)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, vf)
    return out.reshape(B, Hq, hd).astype(q.dtype)


def unpack_rope_rows(pe: jax.Array, page: int) -> jax.Array:
    """Rope keys of ``(..., page // pack, pack * dr)`` rope blocks in
    position order, ``(..., page, dr)``: position ``p`` lives in row
    ``p % (page // pack)``, column group ``p // (page // pack)``."""
    rows, w = pe.shape[-2:]
    pack = page // rows
    pe = pe.reshape(pe.shape[:-1] + (pack, w // pack))
    return jnp.swapaxes(pe, -3, -2).reshape(pe.shape[:-3] + (page,
                                                             w // pack))


def paged_decode_latent_attention(
    q: jax.Array,             # (B, H, r + dr) absorbed query per sequence
    c_pages: jax.Array,       # (L, P, page, r) the stacked c_kv pool
    pe_pages: jax.Array,      # (L, P, page // pack, pack * dr) rope keys
    block_table: jax.Array,   # (B, n_pages) int32
    seq_lens: jax.Array,      # (B,) int32 — live length (pos + 1)
    layer: jax.Array,         # int32 scalar — the layer of the pool to read
    *,
    scale: float,
) -> jax.Array:
    """Gather-then-attend definition of the latent paged decode kernel:
    every head scores ``[q_c | q_pe]`` against the sequence's latent rows
    ``[c_kv | k_pe]`` and mixes their ``c_kv``.  (B, H, r) float32."""
    B = q.shape[0]
    page, r = c_pages.shape[2:]
    T = block_table.shape[1] * page
    c = c_pages[layer, block_table].reshape(B, T, r).astype(jnp.float32)
    pe = unpack_rope_rows(pe_pages[layer, block_table], page)
    pe = pe.reshape(B, T, -1).astype(jnp.float32)
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bhr,btr->bht", qf[..., :r], c) \
        + jnp.einsum("bhd,btd->bht", qf[..., r:], pe)
    mask = jnp.arange(T)[None, :] < seq_lens[:, None]
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", p, c)


# --------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) — chunked scan semantics
# --------------------------------------------------------------------------

def ssd(
    x: jax.Array,               # (B, S, H, P)   inputs per head
    dt: jax.Array,              # (B, S, H)      softplus-activated step sizes
    A: jax.Array,               # (H,)           negative decay rates
    Bm: jax.Array,              # (B, S, G, N)   input matrices (G groups)
    C: jax.Array,               # (B, S, G, N)   output matrices
    *,
    init_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Sequential SSD recurrence (the definition, O(S) steps).

        h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t x_t^T
        y_t = C_t^T h_t          (per head; B/C broadcast over head groups)

    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    Bsz, S, H, P = x.shape
    _, _, G, N = Bm.shape
    rep = H // G

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)   # (B,S,H,N)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2)

    h0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(h, inp):
        xt, dtt, bt, ct = inp            # (B,H,P) (B,H) (B,H,N) (B,H,N)
        decay = jnp.exp(dtt * Af[None])[..., None, None]      # (B,H,1,1)
        upd = (dtt[..., None] * xt)[..., :, None] * bt[:, :, None, :]
        h = decay * h + upd              # (B,H,P,N)
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    hT, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)               # (B,S,H,P)
    return y, hT


def ssd_step(
    x: jax.Array,               # (B, H, P)   one token
    dt: jax.Array,              # (B, H)
    A: jax.Array,               # (H,)
    Bm: jax.Array,              # (B, G, N)
    C: jax.Array,               # (B, G, N)
    state: jax.Array,           # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Single decode step of the SSD recurrence."""
    H = x.shape[1]
    G = Bm.shape[1]
    rep = H // G
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, axis=1)     # (B,H,N)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=1)
    decay = jnp.exp(dtf * A[None].astype(jnp.float32))[..., None, None]
    upd = (dtf[..., None] * xf)[..., None] * Bf[:, :, None, :]
    new_state = decay * state.astype(jnp.float32) + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Cf)
    return y.astype(x.dtype), new_state
