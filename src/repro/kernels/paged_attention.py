"""Block-paged KV decode attention — Pallas kernel for the serving engine.

The engine's dense decode attends one query token per sequence against a
``(B, S_max, Hkv, hd)`` cache, touching ``S_max`` rows no matter how short
the live sequence is.  Here the KV cache lives in fixed-size *pages*,
stacked over the layers as ``(L, P, page, Hkv*hd)`` with the KV heads
merged into the minor dim, and each sequence owns an ordered list of
page indices (its row of ``block_table``).  The kernel walks a
sequence's pages through scalar-prefetched operands — the grid index map
reads the layer and ``block_table[b, j]`` to pick which physical page to
stream next — and runs the classic online-softmax accumulation across
pages, masking the tail of the last live page against ``seq_lens``.
The pool is read where it lies: no layer is sliced out or relaid.

This is the indirection layer a continuous-batching engine needs: slots
can grow page-by-page and the physical pages need not be contiguous; the
kernel never sees anything but the table.

Grid: ``(B, n_pages)`` with pages innermost (sequential) so the
(m, l, acc) online-softmax state lives in VMEM scratch across a
sequence's pages.  One block holds a whole page of one layer, all KV
heads, as a ``(page, Hkv*hd)`` tile; the query enters block-diagonal
over the KV heads (``(Hq, Hkv*hd)``, zero outside its own head's
columns), so one score dot and one value dot serve every GQA group and
the kernel never slices a head out of a tile.  The extra FLOPs (a factor
Hkv) do not matter: decode attention is bound by the K/V bytes it
streams.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _paged_decode_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, page: int,
                         n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                      # (Hq, Hkv*hd)
    k = k_ref[0].astype(jnp.float32)                      # (page, Hkv*hd)
    v = v_ref[0].astype(jnp.float32)

    # q is block-diagonal over the KV heads, so row r scores only against
    # its own head's K columns: one dot covers every GQA group.
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # (Hq, page)
    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    s = jnp.where(kpos < len_ref[b], s, -jnp.inf)

    # online softmax update (page 0 always holds position 0, so m starts
    # finite and fully-masked trailing pages contribute exact zeros)
    m_prev = m_ref[...]                                   # (Hq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                # (Hq, page)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)         # (Hq, Hkv*hd)

    @pl.when(j == n_pages - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q: jax.Array,             # (B, Hq, hd)   one query token per sequence
    k_pages: jax.Array,       # (L, P, page, Hkv*hd) the stacked pool
    v_pages: jax.Array,       # (L, P, page, Hkv*hd)
    block_table: jax.Array,   # (B, n_pages) int32 — physical page per slot
    seq_lens: jax.Array,      # (B,) int32 — live length (pos + 1)
    layer: jax.Array,         # int32 scalar — the layer of the pool to read
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, hd = q.shape
    page, width = k_pages.shape[2:]
    assert width % hd == 0, (q.shape, k_pages.shape)
    Hkv = width // hd
    assert Hq % Hkv == 0, (q.shape, k_pages.shape)
    g = Hq // Hkv
    n_pages = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)

    # A page block spans every KV head of one layer: (page, Hkv*hd) meets
    # the TPU's (8, 128) block rule whatever Hkv and hd are (the last two
    # dims are whole), and the layer dim is squeezed out of the block.
    eye = jnp.eye(Hkv, dtype=q.dtype)
    q_bd = jnp.einsum("bkgd,kj->bkgjd", q.reshape(B, Hkv, g, hd) * scale,
                      eye).reshape(B, Hq, width)
    kv_spec = pl.BlockSpec(
        (None, 1, page, width),
        lambda b, j, tbl, lens, lyr: (lyr[0], tbl[b, j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, Hq, width),
                         lambda b, j, tbl, lens, lyr: (b, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, Hq, width),
                               lambda b, j, tbl, lens, lyr: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),      # running max
            pltpu.VMEM((Hq, 1), jnp.float32),      # running denominator
            pltpu.VMEM((Hq, width), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page, n_pages=n_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="dmath_paged_decode",
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q_bd, k_pages, v_pages)
    # row r of head h holds its output in column block h: keep the diagonal
    h = jnp.arange(Hkv)
    out = out.reshape(B, Hkv, g, Hkv, hd)[:, h, :, h]     # (Hkv, B, g, hd)
    return out.transpose(1, 0, 2, 3).reshape(B, Hq, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# Latent (MLA) paged decode: one latent row per position, shared by heads
# --------------------------------------------------------------------------

def rope_pack(rope_dim: int) -> int:
    """Positions whose rope keys share one row of the rope pool: as many
    as fill 128 lanes (2 at DeepSeek-V2's 64)."""
    return max(1, 128 // rope_dim)


def _paged_latent_kernel(tbl_ref, len_ref, layer_ref, qc_ref, qpe_ref,
                         c_ref, pe_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         page: int, n_pages: int, pack: int, heads: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages past the last live one repeat its block index (no fetch) and
    # skip the math
    @pl.when(j * page < len_ref[b])
    def _page():
        c = c_ref[0]                                       # (page, r)
        nt = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(qc_ref[0], c, nt,
                                preferred_element_type=jnp.float32)
        # the block-diagonal rope query scores every packed column group
        # at once: row group k holds the positions k*page/pack + i
        spe = jax.lax.dot_general(qpe_ref[0], pe_ref[0], nt,
                                  preferred_element_type=jnp.float32)
        s = s + jnp.concatenate(
            [spe[k * heads:(k + 1) * heads] for k in range(pack)], axis=1)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        s = jnp.where(kpos < len_ref[b], s, -jnp.inf)      # (H, page)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)

    @pl.when(j == n_pages - 1)
    def _flush():
        o_ref[0] = acc_ref[...] / l_ref[...]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_latent_attention(
    q: jax.Array,             # (B, H, r + dr) absorbed query per sequence
    c_pages: jax.Array,       # (L, P, page, r) the stacked c_kv pool
    pe_pages: jax.Array,      # (L, P, page // pack, pack * dr) rope keys
    block_table: jax.Array,   # (B, n_pages) int32 — physical page per slot
    seq_lens: jax.Array,      # (B,) int32 — live length (pos + 1), >= 1
    layer: jax.Array,         # int32 scalar — the layer of the pool to read
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """MQA of every head's absorbed query over the sequence's latent rows:
    each page's ``c_kv`` block serves as key (with its rope block) and as
    value, read once for all heads.  Position ``p`` of a page keeps its
    rope key in row ``p % (page // pack)``, column group ``p // (page //
    pack)`` of the page's rope block, so both pools are exact multiples
    of 128 lanes (a 576-wide row would pad to 640).  The grid ``(B,
    n_pages)`` clamps the page index to the slot's last live page, so
    dead pages cost a grid step but no HBM read.  Returns ``(B, H, r)``
    float32."""
    B, H, _ = q.shape
    page, r = c_pages.shape[2:]
    rows, wpe = pe_pages.shape[2:]
    pack = page // rows
    dr = wpe // pack
    n_pages = block_table.shape[1]
    qs = q.astype(jnp.float32) * scale
    qc = qs[..., :r].astype(c_pages.dtype)
    # (B, pack*H, pack*dr): row group k holds q_pe in column group k
    eye = jnp.eye(pack, dtype=jnp.float32)
    qpe = jnp.einsum("bhd,kj->bkhjd", qs[..., r:], eye).reshape(
        B, pack * H, wpe).astype(pe_pages.dtype)

    def page_index(b, j, tbl, lens, lyr):
        last = (lens[b] - 1) // page
        return (lyr[0], tbl[b, jnp.minimum(j, last)], 0, 0)

    slot = lambda b, j, tbl, lens, lyr: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, r), slot),
            pl.BlockSpec((1, pack * H, wpe), slot),
            pl.BlockSpec((None, 1, page, r), page_index),
            pl.BlockSpec((None, 1, rows, wpe), page_index),
        ],
        out_specs=pl.BlockSpec((1, H, r), slot),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),      # running max
            pltpu.VMEM((H, 1), jnp.float32),      # running denominator
            pltpu.VMEM((H, r), jnp.float32),      # output accumulator
        ],
    )
    assert dr * pack == wpe and rows * pack == page, (
        c_pages.shape, pe_pages.shape)
    return pl.pallas_call(
        functools.partial(_paged_latent_kernel, page=page, n_pages=n_pages,
                          pack=pack, heads=H),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="dmath_paged_decode_latent",
    )(block_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), qc, qpe, c_pages,
      pe_pages)
