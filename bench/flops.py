"""Operations and bytes the work requires, computed from shapes.

Counts are of what the algorithm needs, not of what the program happens
to do: no recomputation, logits only where a token is sampled, the
weights read once per device program, and only the live part of the KV
cache.  A roofline share built from them is therefore at most 100%.
All weights and K/V are bf16 (2 bytes).
"""

from __future__ import annotations

from typing import Iterable

from bench.model_config import Shape

BF16 = 2


def train_flops_per_token(s: Shape, seq: int) -> float:
    """Forward and backward FLOPs per token of a causal LM step at
    sequence length ``seq``: 3x the forward (2 FLOPs per weight per
    token, the embedding lookup free) plus causal attention, whose query
    at position t scores and mixes t+1 keys."""
    dense = 2 * (s.layers * s.layer_params + s.d_model * s.vocab)
    attn = s.layers * 4 * s.heads * s.head_dim * (seq + 1) / 2
    return 3.0 * (dense + attn)


def weight_bytes(s: Shape, with_head: bool = True) -> int:
    """bf16 bytes of the weights a decode or prefill program must read:
    every layer's matmul weights and the unembedding (the embedding
    table is only gathered from, a few rows per token)."""
    return BF16 * (s.layers * s.layer_params
                   + (s.d_model * s.vocab if with_head else 0))


def decode_tick(s: Shape, ctx: Iterable[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over the live slots, ``ctx``
    holding each slot's context length including the new token."""
    ctx = list(ctx)
    n = len(ctx)
    if not n:
        return 0.0, 0.0
    flops = 2.0 * (s.layers * s.layer_params + s.d_model * s.vocab) * n \
        + sum(s.layers * 4 * s.heads * s.head_dim * c for c in ctx)
    kv = sum(ctx) * s.kv_bytes_per_token
    return flops, float(weight_bytes(s) + kv)


def prefill_chunk(s: Shape, start: int, n: int, last: bool
                  ) -> tuple[float, float]:
    """(FLOPs, bytes) of prefilling ``n`` prompt tokens at positions
    ``start..start+n-1`` of one sequence; the head runs only for the
    prompt's last token, when ``last``."""
    attn = sum(s.layers * 4 * s.heads * s.head_dim * (p + 1)
               for p in range(start, start + n))
    flops = 2.0 * s.layers * s.layer_params * n + attn \
        + (2.0 * s.d_model * s.vocab if last else 0.0)
    kv = (start + n) * s.kv_bytes_per_token
    return flops, float(weight_bytes(s, with_head=last) + kv)


def paged_decode_call(s: Shape, seq_lens: Iterable[int]
                      ) -> tuple[float, float]:
    """(FLOPs, bytes) of one call of the paged decode attention kernel
    (one layer): each slot's query reads its live K and V, scores and
    mixes them; the bf16 query goes in and the bf16 output comes out."""
    seq_lens = list(seq_lens)
    hd, hq, hkv = s.head_dim, s.heads, s.kv_heads
    flops = float(sum(4 * hq * hd * n for n in seq_lens))
    kv = sum(seq_lens) * 2 * hkv * hd * BF16
    qo = len(seq_lens) * 2 * hq * hd * BF16
    return flops, float(kv + qo)


def roofline_seconds(flops: float, nbytes: float, peak) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak.flops_per_s, nbytes / peak.hbm_bytes_per_s)
