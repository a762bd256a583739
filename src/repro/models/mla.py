"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) over a
latent paged cache.

Per token the layer keeps one latent row ``[c_kv | k_pe]``: the RMS-normed
``kv_lora_rank`` compression of K and V and the roped
``qk_rope_head_dim`` key that every head shares.  The pool is a pair of
bf16 arrays read and written in place like the GQA pool
(:mod:`repro.models.attention`): ``c_pages`` ``(L, P, page,
kv_lora_rank)`` and ``pe_pages`` ``(L, P, page // pack, pack *
qk_rope_head_dim)``, whose rows pack the rope keys of ``pack`` positions
into 128 lanes (:func:`repro.kernels.paged_attention.rope_pack`).  Both
are exact in the chip's lanes; one ``(page, 576)`` array would pad each
row to 640.

- Prefill decompresses each head's key ``[c_kv W_uk | k_pe]`` and value
  ``c_kv W_uv`` from the sequence's latent rows and runs the flash body.
- Decode uses the absorbed form: the query ``[q_nope W_uk^T | q_pe]``
  scores the latent rows directly (MQA over the latent, one page tile
  serving all heads as key and, its first ``kv_lora_rank`` columns, as
  value), and ``W_uv`` is applied to the attended latent afterwards.

Rotary frequencies follow YaRN (arXiv:2309.00071) where the config has
``yarn``, with the softmax scale multiplied by ``mscale(factor,
mscale_all_dim)**2`` as DeepSeek-V2 does.  The rope part of q and k uses
the half-split layout; the published weights interleave it, which is a
fixed permutation of the ``wq`` and ``wkv_a`` rope columns.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import precision
from repro.core.layout import Layout
from repro.models import layers
from repro.models.params import ParamSpec


def mla_specs(cfg, plan, mesh) -> dict:
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rep = lambda n: Layout.replicated(n)
    return {
        "wq": ParamSpec((D, H, dn + dr), plan.attn_qkv((D, H, dn + dr),
                                                       mesh)),
        "wkv_a": ParamSpec((D, r + dr), rep(2)),
        "kv_norm": ParamSpec((r,), rep(1), init="ones"),
        "wk_b": ParamSpec((r, H, dn), rep(3)),
        "wv_b": ParamSpec((r, H, dv), rep(3)),
        "wo": ParamSpec((H, dv, D), plan.attn_out((H, dv, D), mesh),
                        init="scaled",
                        scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }


# --------------------------------------------------------------------------
# YaRN rotary frequencies and softmax scale
# --------------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(cfg) -> np.ndarray:
    """(qk_rope_head_dim // 2,) float32 inverse frequencies: plain rotary,
    or YaRN's blend of interpolated and extrapolated frequencies with a
    linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow``."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    y = cfg.yarn
    if y is None:
        return extra.astype(np.float32)
    inter = extra / y.factor

    def corr_dim(rot):
        return (dim * math.log(y.original_max_position
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                      # 1: extrapolate (high freq)
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rope_mscale(cfg) -> float:
    """The factor YaRN puts on cos and sin (1 when mscale equals
    mscale_all_dim, as in DeepSeek-V2)."""
    y = cfg.yarn
    if y is None:
        return 1.0
    return yarn_mscale(y.factor, y.mscale) / yarn_mscale(
        y.factor, y.mscale_all_dim)


def softmax_scale(cfg) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def rope(x: jax.Array, positions: jax.Array, cfg) -> jax.Array:
    """Half-split rotary of x (..., S, H, dr) at positions (S,) or
    broadcastable, with YaRN frequencies; float32 out."""
    half = x.shape[-1] // 2
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        rope_inv_freq(cfg))
    m = rope_mscale(cfg)
    cos = (jnp.cos(ang) * m)[..., None, :]
    sin = (jnp.sin(ang) * m)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --------------------------------------------------------------------------
# projections
# --------------------------------------------------------------------------

def _project(x, p, cfg, positions, policy):
    """(q_nope (B,S,H,dn), q_pe roped (B,S,H,dr), latent row (B,S,r+dr)
    with c_kv normed and k_pe roped), all float32."""
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = precision.einsum("bsd,dhk->bshk", x, p["wq"], policy=policy)
    kv = precision.einsum("bsd,dr->bsr", x, p["wkv_a"], policy=policy)
    c = layers.rms_norm(kv[..., :r].astype(jnp.float32), p["kv_norm"],
                        cfg.norm_eps)
    k_pe = rope(kv[..., None, r:], positions, cfg)[..., 0, :]
    q_pe = rope(q[..., dn:], positions, cfg)
    return (q[..., :dn].astype(jnp.float32), q_pe,
            jnp.concatenate([c, k_pe], -1))


def _write(c_pages, pe_pages, layer, phys, off, row, cfg):
    """Scatter latent rows (n, r + dr) at page ``phys``, offset ``off`` of
    layer ``layer``: c_kv as a row of ``c_pages``, k_pe into its packed
    slot of ``pe_pages``."""
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    rows = pe_pages.shape[2]
    c_pages = c_pages.at[layer, phys, off].set(row[:, :r].astype(
        c_pages.dtype))
    idx = jnp.stack([jnp.broadcast_to(layer, phys.shape), phys, off % rows,
                     (off // rows) * dr], -1).astype(jnp.int32)
    pe_pages = jax.lax.scatter(
        pe_pages, idx, row[:, r:].astype(pe_pages.dtype),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1, 2),
            scatter_dims_to_operand_dims=(0, 1, 2, 3)),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    return c_pages, pe_pages


def prefill_chunk_paged(
    x: jax.Array,                  # (1, C, D) one prompt chunk, end-padded
    p: dict,
    cfg,
    pool: Tuple[jax.Array, jax.Array],   # (c_pages, pe_pages), stacked
    layer: jax.Array,              # int32 scalar: this layer's index
    table_row: jax.Array,          # (n_pages,) int32 logical -> physical
    start: jax.Array,              # scalar: absolute position of chunk[0]
    *,
    policy,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One prefill chunk of layer ``layer`` in the decompressed form.

    Writes the chunk's latent rows into the sequence's pages of that
    layer, gathers the sequence's latent rows in logical page order,
    decompresses every head's K and V from them and runs the causal flash
    body at ``q_offset=start`` (the same padding argument as
    :func:`repro.models.attention.prefill_chunk_paged`)."""
    from repro.kernels.ref import unpack_rope_rows

    C = x.shape[1]
    c_pages, pe_pages = pool
    page, r = c_pages.shape[2:]
    dr = cfg.qk_rope_head_dim
    positions = start + jnp.arange(C)
    q_nope, q_pe, row = _project(x, p, cfg, positions, policy)
    c_pages, pe_pages = _write(c_pages, pe_pages, layer,
                               table_row[positions // page],
                               positions % page, row[0], cfg)

    c = c_pages[layer, table_row].reshape(1, -1, r)            # (1,T,r)
    k_pe = unpack_rope_rows(pe_pages[layer, table_row], page).reshape(
        1, -1, 1, dr)                                          # (1,T,1,dr)
    k_nope = precision.einsum("btr,rhk->bthk", c, p["wk_b"], policy=policy)
    v = precision.einsum("btr,rhk->bthk", c, p["wv_b"], policy=policy)
    k = jnp.concatenate(
        [k_nope.astype(c.dtype),
         jnp.broadcast_to(k_pe, k_nope.shape[:3] + (dr,))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1).astype(c.dtype)
    out = layers.flash_attention_jnp(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.astype(c.dtype).transpose(0, 2, 1, 3),
        causal=True, scale=softmax_scale(cfg), q_offset=start,
        bq=min(q_chunk, C), bkv=kv_chunk,
    ).transpose(0, 2, 1, 3)                                    # (1,C,H,dv)
    y = precision.einsum("bshk,hkd->bsd", out, p["wo"], policy=policy)
    return y.astype(x.dtype), (c_pages, pe_pages)


def decode_paged(
    x: jax.Array,                  # (B, 1, D)
    p: dict,
    cfg,
    pool: Tuple[jax.Array, jax.Array],   # (c_pages, pe_pages), stacked
    layer: jax.Array,              # int32 scalar: this layer's index
    block_table: jax.Array,        # (B, n_pages) int32 logical -> physical
    pos: jax.Array,                # (B,) or scalar position of the new token
    *,
    policy,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decode step of layer ``layer`` in the absorbed form: the new
    latent row scatters into the pool, the absorbed query attends the
    sequence's latent rows through
    :func:`repro.kernels.ops.paged_decode_latent_attention`, and ``W_uv``
    then ``wo`` map the attended latent back."""
    from repro.kernels import ops as kops

    B = x.shape[0]
    c_pages, pe_pages = pool
    page = c_pages.shape[2]
    pos_b = jnp.broadcast_to(pos, (B,))
    q_nope, q_pe, row = _project(x, p, cfg, pos_b[:, None], policy)
    c_pages, pe_pages = _write(c_pages, pe_pages, layer,
                               block_table[jnp.arange(B), pos_b // page],
                               pos_b % page, row[:, 0], cfg)

    q_lat = precision.einsum("bhk,rhk->bhr", q_nope[:, 0], p["wk_b"],
                             policy=policy)                    # (B,H,r)
    q = jnp.concatenate([q_lat.astype(jnp.float32), q_pe[:, 0]], -1)
    o = kops.paged_decode_latent_attention(
        q, c_pages, pe_pages, block_table, (pos_b + 1).astype(jnp.int32),
        layer, scale=softmax_scale(cfg))                       # (B,H,r) f32
    out = precision.einsum("bhr,rhk->bhk", o, p["wv_b"], policy=policy)
    y = precision.einsum("bhk,hkd->bd", out, p["wo"], policy=policy)
    return y[:, None].astype(x.dtype), (c_pages, pe_pages)
