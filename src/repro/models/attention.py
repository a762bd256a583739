"""Attention block with planner-selected parallelism.

Modes (DESIGN §4):

- ``head_tp``: heads sharded over "model" (classic Megatron TP) — used when
  both Hq and Hkv divide the axis.  Pure GSPMD: constraints on the head dim.
- ``sp``: sequence parallel over "model" — the remapping-service fallback
  when head counts don't divide.  Implemented with shard_map: each model
  shard owns a contiguous q-sequence block, gathers K/V (all-gather over
  "model"), and runs the local flash body with a global q_offset.
- decode: flash-decoding for every arch — the KV cache is sharded on the
  *sequence* dim; softmax stats are combined by GSPMD.

The KV cache convention is (B, S, Hkv, hd) seq-major, matching the decode
layout; prefill writes it with one relayout (all-to-all for head_tp).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import manual, precision
from repro.core.layout import Layout, constrain
from repro.core.planner import ParallelPlan
from repro.models import layers
from repro.models.params import ParamSpec


def attn_specs(cfg, plan: ParallelPlan, mesh) -> dict:
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s = {
        "wq": ParamSpec((D, H, hd), plan.attn_qkv((D, H, hd), mesh)),
        "wk": ParamSpec((D, Hkv, hd), plan.attn_qkv((D, Hkv, hd), mesh)),
        "wv": ParamSpec((D, Hkv, hd), plan.attn_qkv((D, Hkv, hd), mesh)),
        "wo": ParamSpec((H, hd, D), plan.attn_out((H, hd, D), mesh),
                        init="scaled",
                        scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        hl = (plan.tp_axis if plan.attn_mode == "head_tp" else None)
        s["bq"] = ParamSpec((H, hd), Layout((hl, None)), init="zeros")
        s["bk"] = ParamSpec((Hkv, hd), Layout((hl, None)), init="zeros")
        s["bv"] = ParamSpec((Hkv, hd), Layout((hl, None)), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), Layout((None,)), init="ones")
        s["k_norm"] = ParamSpec((hd,), Layout((None,)), init="ones")
    return s


def _use(layout: Layout, plan: ParallelPlan) -> Layout:
    return layout.drop_axis(plan.fsdp_axis) if plan.fsdp else layout


def _qkv(x, p, cfg, plan, positions, policy, constrain_weights=True):
    """Projections + qk-norm + rotary.  x: (B,S,D) in hidden layout.

    ``constrain_weights=False`` inside shard_map bodies (values are local
    there; the gather already happened at the shard_map boundary).
    """
    if constrain_weights:
        wq = constrain(p["wq"], _use_spec(cfg, plan, "q"))
        wk = constrain(p["wk"], _use_spec(cfg, plan, "kv"))
        wv = constrain(p["wv"], _use_spec(cfg, plan, "kv"))
    else:
        wq, wk, wv = p["wq"], p["wk"], p["wv"]
    q = precision.einsum("bsd,dhk->bshk", x, wq, policy=policy)
    k = precision.einsum("bsd,dhk->bshk", x, wk, policy=policy)
    v = precision.einsum("bsd,dhk->bshk", x, wv, policy=policy)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = layers.rotary(q, positions, cfg.rope_theta)
    k = layers.rotary(k, positions, cfg.rope_theta)
    return q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype)


def _use_spec(cfg, plan, kind: str) -> Layout:
    if plan.attn_mode == "head_tp":
        head = plan.tp_axis
    else:
        head = None
    return Layout((None, head, None))


def forward(
    x: jax.Array,                  # (B, S, D) hidden layout per plan
    p: dict,
    cfg,
    plan: ParallelPlan,
    mesh,
    *,
    policy,
    window: Optional[Union[int, jax.Array]] = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    with_cache: bool = False,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Full-sequence attention (train / prefill)."""
    B, S, D = x.shape
    positions = jnp.arange(S)

    if plan.attn_mode == "head_tp" and plan.seq_parallel_residual:
        y, k, v = _tp_attention_shardmap(
            x, p, cfg, plan, mesh, policy=policy, window=window,
            q_chunk=q_chunk, kv_chunk=kv_chunk)
        return y, ((k, v) if with_cache else None)

    if plan.attn_mode == "head_tp":
        q, k, v = _qkv(x, p, cfg, plan, positions, policy)
        q = constrain(q, plan.heads_act())
        k = constrain(k, plan.heads_act())
        v = constrain(v, plan.heads_act())
        out = layers.flash_attention_jnp(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=True, window=window, softcap=cfg.attn_softcap,
            bq=q_chunk, bkv=kv_chunk,
        ).transpose(0, 2, 1, 3)                                 # (B,S,H,hd)
        out = constrain(out, plan.heads_act())
    else:
        out, k, v = _sp_attention(x, p, cfg, plan, mesh, policy=policy,
                                  window=window, q_chunk=q_chunk,
                                  kv_chunk=kv_chunk)

    wo = constrain(p["wo"], Layout((plan.tp_axis if plan.attn_mode ==
                                    "head_tp" else None, None, None)))
    y = precision.einsum("bshk,hkd->bsd", out, wo, policy=policy)
    y = constrain(y.astype(x.dtype), plan.hidden())

    cache = None
    if with_cache:
        # seq-major cache in flash-decoding layout (relayout if head-TP)
        cache = (k, v)
    return y, cache


def _tp_attention_shardmap(x, p, cfg, plan, mesh, *, policy, window,
                           q_chunk, kv_chunk):
    """Head-TP attention with EXPLICIT bf16 collectives (shard_map).

    AG the seq-sharded bf16 residual once, project q/k/v for the LOCAL
    head shard, flash over the full sequence, partial out-projection,
    bf16 reduce-scatter back onto the sequence shards.  GSPMD's version
    moved fp32 tensors on every one of these boundaries (§Perf iter 5).
    """
    from jax.sharding import PartitionSpec as P
    tp = plan.tp_axis
    B, S, D = x.shape
    positions = jnp.arange(S)

    head_specs = {"wq": P(None, tp, None), "wk": P(None, tp, None),
                  "wv": P(None, tp, None), "wo": P(tp, None, None)}
    for extra, spec in (("bq", P(tp, None)), ("bk", P(tp, None)),
                        ("bv", P(tp, None)), ("q_norm", P(None)),
                        ("k_norm", P(None))):
        if extra in p:
            head_specs[extra] = spec

    def body(xl, pl):
        xg = jax.lax.all_gather(xl, tp, axis=1, tiled=True)     # bf16
        q, k, v = _qkv(xg, pl, cfg, plan, positions, policy,
                       constrain_weights=False)
        out = layers.flash_attention_jnp(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=True, window=window, softcap=cfg.attn_softcap,
            bq=q_chunk, bkv=kv_chunk,
        ).transpose(0, 2, 1, 3)
        y = precision.einsum("bshk,hkd->bsd", out, pl["wo"], policy=policy)
        y = jax.lax.psum_scatter(y.astype(xl.dtype), tp,
                                 scatter_dimension=1, tiled=True)
        return y, k, v

    kv_spec = P(plan.batch_axes, None, tp, None)
    y, k, v = manual.shard_map(
        body, mesh=mesh,
        in_specs=(P(plan.batch_axes, tp, None),
                  {k_: head_specs[k_] for k_ in p}),
        out_specs=(P(plan.batch_axes, tp, None), kv_spec, kv_spec),
    )(x, dict(p))
    return y, k, v


def _sp_attention(x, p, cfg, plan, mesh, *, policy, window, q_chunk,
                  kv_chunk):
    """Sequence-parallel attention via shard_map over the TP axis.

    x arrives seq-sharded P(batch, model, -).  Each shard computes its
    local q block against the gathered K/V with a global q_offset — the
    relayout service in action (all-gather of K/V is the only collective).
    """
    B, S, D = x.shape
    tp = plan.tp_axis
    ax_size = mesh.shape[tp]
    s_loc = S // ax_size

    x_spec = plan.hidden(seq_sharded=True).spec
    p_specs = {k_: Layout.replicated(v_.ndim).spec for k_, v_ in p.items()}

    def body(xl, pl):
        idx = jax.lax.axis_index(tp)
        positions = idx * s_loc + jnp.arange(s_loc)
        q, k, v = _qkv(xl, pl, cfg, plan, positions, policy,
                       constrain_weights=False)
        kg = jax.lax.all_gather(k, tp, axis=1, tiled=True)     # (B,S,Hkv,hd)
        vg = jax.lax.all_gather(v, tp, axis=1, tiled=True)
        out = layers.flash_attention_jnp(
            q.transpose(0, 2, 1, 3), kg.transpose(0, 2, 1, 3),
            vg.transpose(0, 2, 1, 3),
            causal=True, window=window, softcap=cfg.attn_softcap,
            q_offset=idx * s_loc, bq=min(q_chunk, s_loc), bkv=kv_chunk,
        ).transpose(0, 2, 1, 3)
        return out, k, v

    out_spec = plan.seq_act().spec
    out, k, v = manual.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, p_specs),
        out_specs=(out_spec, out_spec, out_spec),
    )(x, {k_: p[k_] for k_ in p})
    return out, k, v


def decode_ring(
    x: jax.Array,                  # (B, 1, D)
    p: dict,
    cfg,
    plan: ParallelPlan,
    k_ring: jax.Array,             # (B, W, Hkv, hd) sliding-window ring
    v_ring: jax.Array,
    pos: jax.Array,
    *,
    policy,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for a LOCAL (sliding-window) layer: O(window)
    cache instead of O(seq) — gemma3's 5:1 pattern is built for this.

    ``pos`` may be scalar (lockstep batch) or per-slot ``(B,)`` (ragged
    serving batches); the per-slot path scatters each row's token into
    its own ring slot."""
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    q, k, v = _qkv(x, p, cfg, plan, positions, policy)
    W = k_ring.shape[1]
    slot = jnp.mod(pos, W)
    if pos.ndim == 0:
        k_ring = jax.lax.dynamic_update_slice_in_dim(
            k_ring, k.astype(k_ring.dtype), slot, axis=1)
        v_ring = jax.lax.dynamic_update_slice_in_dim(
            v_ring, v.astype(v_ring.dtype), slot, axis=1)
    else:
        b_idx = jnp.arange(x.shape[0])
        k_ring = k_ring.at[b_idx, slot].set(k[:, 0].astype(k_ring.dtype))
        v_ring = v_ring.at[b_idx, slot].set(v[:, 0].astype(v_ring.dtype))
    out = layers.decode_attention_ring(
        q.transpose(0, 2, 1, 3), k_ring, v_ring, pos,
        softcap=cfg.attn_softcap)
    out = out.transpose(0, 2, 1, 3)
    y = precision.einsum("bshk,hkd->bsd", out, p["wo"], policy=policy)
    return y.astype(x.dtype), k_ring, v_ring


def decode_paged(
    x: jax.Array,                  # (B, 1, D)
    p: dict,
    cfg,
    plan: ParallelPlan,
    k_pages: jax.Array,            # (L, P, page, Hkv*hd) stacked page pool
    v_pages: jax.Array,
    layer: jax.Array,              # int32 scalar: this layer's index
    block_table: jax.Array,        # (B, n_pages) int32 logical -> physical
    pos: jax.Array,                # scalar or (B,) position of the new token
    *,
    policy,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step of layer ``layer`` against the stacked paged pool.

    The new token scatters into row ``[layer, block_table[b, pos//page],
    pos % page]`` of the pool; attention then walks the sequence's pages
    of that layer through :func:`repro.kernels.ops.paged_decode_attention`
    (the Pallas kernel where it lowers, the gather-based oracle
    elsewhere).  The pool is touched only where it changes: B rows
    written, the live pages read.  ``pos`` may be scalar (lockstep
    static-batch decode) or per-slot ``(B,)`` (continuous batching);
    every position ``<= pos[b]`` is live, so ``seq_lens`` is simply
    ``pos + 1`` per slot.
    """
    from repro.kernels import ops as kops

    B = x.shape[0]
    page, width = k_pages.shape[2:]
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    q, k, v = _qkv(x, p, cfg, plan, positions, policy)         # (B,1,H,hd)

    pos_b = jnp.broadcast_to(pos, (B,))
    phys = block_table[jnp.arange(B), pos_b // page]           # (B,)
    off = pos_b % page
    k_pages = k_pages.at[layer, phys, off].set(
        k[:, 0].reshape(B, width).astype(k_pages.dtype))
    v_pages = v_pages.at[layer, phys, off].set(
        v[:, 0].reshape(B, width).astype(v_pages.dtype))

    seq_lens = (pos_b + 1).astype(jnp.int32)
    out = kops.paged_decode_attention(
        q[:, 0].astype(k_pages.dtype), k_pages, v_pages,
        block_table, seq_lens, layer)                          # (B,H,hd)
    y = precision.einsum("bshk,hkd->bsd", out[:, None].astype(q.dtype),
                         p["wo"], policy=policy)
    return y.astype(x.dtype), k_pages, v_pages


def prefill_chunk_paged(
    x: jax.Array,                  # (1, C, D) one prompt chunk, end-padded
    p: dict,
    cfg,
    plan: ParallelPlan,
    k_pages: jax.Array,            # (L, P, page, Hkv*hd) stacked page pool
    v_pages: jax.Array,
    layer: jax.Array,              # int32 scalar: this layer's index
    table_row: jax.Array,          # (n_pages,) int32 logical -> physical
    start: jax.Array,              # scalar: absolute position of chunk[0]
    *,
    policy,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fixed-size prefill chunk of layer ``layer`` for ONE sequence
    against the stacked paged pool.

    Scatters the chunk's K/V into the sequence's pages of that layer
    (through the block table, so the allocator may hand out pages in any
    order), gathers the sequence's row back in LOGICAL page order, and
    runs the flash body with ``q_offset=start``.  Only the chunk's rows
    are written and only the sequence's row is read.  Correctness of the
    padding/garbage regions:

    - end-padding positions ``>= start + n_real`` are beyond every real
      query's causal horizon, so their scores are masked (their K/V lands
      either in the row's own later pages — overwritten by the next chunk
      or by decode before any query attends that position — or in the
      NULL page when the tail page is unallocated);
    - the gather is by logical order, so attention is invariant to the
      physical page permutation — the static slot-major table and the
      continuous free-list allocator produce bit-identical outputs.
    """
    C = x.shape[1]
    page, width = k_pages.shape[2:]
    positions = start + jnp.arange(C)
    q, k, v = _qkv(x, p, cfg, plan, positions, policy)         # (1,C,H,hd)

    page_idx = positions // page
    phys = table_row[page_idx]                                 # (C,)
    off = positions % page
    k_pages = k_pages.at[layer, phys, off].set(
        k[0].reshape(C, width).astype(k_pages.dtype))
    v_pages = v_pages.at[layer, phys, off].set(
        v[0].reshape(C, width).astype(v_pages.dtype))

    row = (1, table_row.shape[0] * page) + k.shape[2:]
    k_row = k_pages[layer, table_row].reshape(row)             # (1,T,Hkv,hd)
    v_row = v_pages[layer, table_row].reshape(row)
    out = layers.flash_attention_jnp(
        q.transpose(0, 2, 1, 3), k_row.transpose(0, 2, 1, 3),
        v_row.transpose(0, 2, 1, 3),
        causal=True, softcap=cfg.attn_softcap, q_offset=start,
        bq=min(q_chunk, C), bkv=kv_chunk,
    ).transpose(0, 2, 1, 3)                                    # (1,C,H,hd)
    y = precision.einsum("bshk,hkd->bsd", out, p["wo"], policy=policy)
    return y.astype(x.dtype), k_pages, v_pages


def decode(
    x: jax.Array,                  # (B, 1, D)
    p: dict,
    cfg,
    plan: ParallelPlan,
    k_cache: jax.Array,            # (B, T, Hkv, hd) seq-sharded
    v_cache: jax.Array,
    pos: jax.Array,                # scalar or (B,) position of the new token
    *,
    policy,
    window: Optional[Union[int, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step: update cache at ``pos``, flash-decode attention.

    Per-slot ``(B,)`` positions scatter each row's token into its own
    cache slot — the ragged-batch serving path (no lockstep max-pos)."""
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    q, k, v = _qkv(x, p, cfg, plan, positions, policy)         # (B,1,H,hd)

    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), pos, axis=1)
    else:
        b_idx = jnp.arange(x.shape[0])
        k_cache = k_cache.at[b_idx, pos].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[b_idx, pos].set(v[:, 0].astype(v_cache.dtype))

    out = layers.decode_attention(
        q.transpose(0, 2, 1, 3), k_cache, v_cache, pos,
        window=window, softcap=cfg.attn_softcap)               # (B,H,1,hd)
    out = out.transpose(0, 2, 1, 3)                            # (B,1,H,hd)
    y = precision.einsum("bshk,hkd->bsd", out, p["wo"], policy=policy)
    return y.astype(x.dtype), k_cache, v_cache
