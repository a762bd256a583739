"""Operations and bytes that latent-attention MoE decoding requires,
computed from the shapes of :class:`bench.reference.deepseek_v2.MLAShape`.

As in ``bench/flops.py``: counts are of what the algorithm needs, weights
read once per decode step, only the live part of the latent cache, so a
roofline share built from them is at most 100%.  Weights and latent rows
are bf16 (2 bytes), the router float32.  Every held expert's weights are
counted as read: with T tokens of top-k over E experts an expert is left
with no token with probability (1 - k/E)^T, 4% at 32 tokens of top-6 over
64.  Expert FLOPs are counted for the tokens routed to held experts,
k * held / E of an expert per token (the share uniform routing gives).
"""

from __future__ import annotations

from typing import Iterable

from bench.reference.deepseek_v2 import MLAShape

BF16, F32 = 2, 4


def weight_bytes(s: MLAShape) -> int:
    """Bytes a decode step must read: every layer's attention and norm
    weights, the dense MLP, each MoE layer's router, held experts and
    shared experts, the final norm and the head (the embedding is only
    gathered from)."""
    D = s.d_model
    norms = s.layers * (2 * D + s.kv_rank) + D
    dense = s.first_dense * 3 * D * s.d_ff
    moe = s.moe_layers * ((s.held[1] + s.shared) * s.expert_params)
    return BF16 * (s.layers * s.attn_params + norms + dense + moe
                   + D * s.vocab) + F32 * s.moe_layers * D * s.experts


def flops_per_token(s: MLAShape) -> float:
    """Matmul FLOPs of one decoded token outside attention's scores."""
    D = s.d_model
    routed = s.top_k * s.held[1] / s.experts
    per_moe = (routed + s.shared) * s.expert_params + D * s.experts
    return 2.0 * (s.layers * s.attn_params + s.first_dense * 3 * D * s.d_ff
                  + s.moe_layers * per_moe + D * s.vocab)


def attn_flops(s: MLAShape, ctx: int) -> float:
    """Absorbed attention of one query over ``ctx`` latent rows, all
    layers: scores over r + rope columns and the mix of r."""
    return s.layers * s.heads * ctx * 2.0 * (s.latent_width + s.kv_rank)


def decode_tick(s: MLAShape, ctx: Iterable[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over the live slots, ``ctx``
    holding each slot's context length including the new token."""
    ctx = list(ctx)
    if not ctx:
        return 0.0, 0.0
    flops = flops_per_token(s) * len(ctx) + sum(attn_flops(s, c)
                                                for c in ctx)
    return flops, float(weight_bytes(s) + sum(ctx)
                        * s.latent_bytes_per_token)


def latent_decode_call(s: MLAShape, seq_lens: Iterable[int]
                       ) -> tuple[float, float]:
    """(FLOPs, bytes) of one call of the latent decode kernel (one layer):
    each slot's bf16 query reads its live latent rows, scores and mixes
    them, and writes its float32 attended latent."""
    seq_lens = list(seq_lens)
    flops = sum(attn_flops(s, n) for n in seq_lens) / s.layers
    rows = sum(seq_lens) * s.latent_width * BF16
    qo = len(seq_lens) * s.heads * (s.latent_width * BF16 + s.kv_rank * F32)
    return float(flops), float(rows + qo)
