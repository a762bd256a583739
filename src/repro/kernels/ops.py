"""Dispatch layer: Pallas kernel on TPU, interpret-mode or jnp oracle on CPU.

Model code calls these wrappers; the backend decision (Mosaic kernel vs
interpret-mode kernel vs pure-jnp reference) is made once here.  This is
the same role dMath's kernel-selection layer plays (§4.1: the library picks
the algorithm; the asterisked results show the fallback firing).

Two gates sit between a call and a fused kernel:

1. **availability** — :func:`pallas_supported` probes ONCE whether a tiny
   Pallas kernel actually lowers and runs on this backend (lowering
   errors cannot be caught inside an outer jit trace, so the decision
   must happen before tracing).  On the CPU a requested ``pallas`` mode
   demotes to ``ref`` when the probe fails, counted in ``repro.obs``
   (``kernels.fallback.*``); on a TPU backend the failure raises.
2. **roofline** — :mod:`repro.kernels.roofline` decides per call-shape
   whether the fusion pays: fused kernels win on memory-bound shapes by
   eliminating HBM round trips; on compute-bound shapes XLA's reference
   composition already keeps the MXU busy and dispatch keeps it.

Every decision lands in :func:`dispatch_report` so BENCH_* snapshots can
record which fused kernels were active for the measured cell.

Env/config knobs:
  REPRO_KERNELS = "pallas" | "interpret" | "ref"   (default: pallas on TPU,
                                                    ref elsewhere;
                                                    interpret is refused
                                                    on TPU)
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs as obs_mod

from . import flash_attention as _fa
from . import fused as _fused
from . import gemm as _gemm
from . import paged_attention as _paged
from . import ref as _ref
from . import roofline as _roofline
from . import ssd_scan as _ssd


def backend() -> str:
    mode = os.environ.get("REPRO_KERNELS")
    on_tpu = jax.default_backend() == "tpu"
    if mode == "interpret" and on_tpu:
        raise ValueError("REPRO_KERNELS=interpret on a TPU backend: the "
                         "interpreter would stand in for the chip's kernels")
    if mode:
        return mode
    return "pallas" if on_tpu else "ref"


# --------------------------------------------------------------------------
# Availability probe: CPU demotes to ref, TPU raises
# --------------------------------------------------------------------------

_PROBED = False
_PROBE_ERROR: Optional[Exception] = None


def _probe_error() -> Optional[Exception]:
    """Why a minimal Pallas kernel fails to lower and run here (cached);
    None when it runs."""
    global _PROBED, _PROBE_ERROR
    if not _PROBED:
        try:
            from jax.experimental import pallas as pl

            def _probe(x_ref, o_ref):
                o_ref[...] = x_ref[...] + 1.0

            x = jnp.zeros((8, 128), jnp.float32)
            out = pl.pallas_call(
                _probe, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
            jax.block_until_ready(out)
        except Exception as e:            # backend-specific lowering error
            _PROBE_ERROR = e
        _PROBED = True
    return _PROBE_ERROR


def pallas_supported() -> bool:
    """Can a Pallas kernel lower AND execute on this backend?  Cached."""
    return _probe_error() is None


def resolve(op: str = "") -> str:
    """Effective mode for one op call.

    ``pallas`` demotes to ``ref`` when the probe fails on a backend
    without Mosaic (the CPU), with the demotion counted in obs.  On a TPU
    backend a failed probe raises: there the reference would hide the
    chip's kernels.
    """
    mode = backend()
    if mode == "pallas" and not pallas_supported():
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "Pallas kernels do not lower on this TPU backend"
            ) from _probe_error()
        obs = obs_mod.get_active()
        if obs.enabled:
            obs.counter("kernels.fallback.pallas_unavailable").inc()
            if op:
                obs.counter(f"kernels.fallback.{op}").inc()
        return "ref"
    return mode


# --------------------------------------------------------------------------
# Dispatch report (BENCH_* meta: which fused kernels were active)
# --------------------------------------------------------------------------

_DECISIONS: Dict[str, Dict] = {}


def _record(d: "_roofline.GateDecision", mode: str) -> bool:
    """Log a gate decision (latest per op wins).  Returns whether the
    fused kernel actually runs (gate AND backend)."""
    active = d.fused and mode in ("pallas", "interpret")
    _DECISIONS[d.op] = {**d.to_dict(), "mode": mode, "active": active}
    return active


def dispatch_report() -> Dict[str, Dict]:
    """Latest gate decision per fused op (for snapshot meta)."""
    return {"backend": backend(),
            "pallas_supported": pallas_supported(),
            "ops": dict(sorted(_DECISIONS.items()))}


# --------------------------------------------------------------------------
# Original ops (PRs 1-7): GEMM / flash attention / SSD
# --------------------------------------------------------------------------

def matmul(a, b, out_dtype=None, *, bm=256, bn=256, bk=512):
    mode = resolve("matmul")
    if mode == "ref":
        return _ref.matmul(a, b, out_dtype)
    return _gemm.matmul(a, b, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
                        interpret=(mode == "interpret"))


def attention(q, k, v, *, causal=True, window=None, softcap=None,
              scale=None, q_offset=0, bq=256, bkv=256):
    mode = resolve("attention")
    if mode == "ref":
        return _ref.attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, scale=scale, q_offset=q_offset)
    return _fa.attention(q, k, v, causal=causal, window=window,
                         softcap=softcap, scale=scale, q_offset=q_offset,
                         bq=bq, bkv=bkv, interpret=(mode == "interpret"))


def ssd(x, dt, A, Bm, C, *, chunk=256, init_state=None
        ) -> Tuple[jax.Array, jax.Array]:
    mode = resolve("ssd")
    if mode == "ref" or init_state is not None:
        # the kernel path has no initial-state input (training starts at 0);
        # chunked serving with carry-in uses the oracle semantics.
        return _ref.ssd(x, dt, A, Bm, C, init_state=init_state)
    return _ssd.ssd(x, dt, A, Bm, C, chunk=chunk,
                    interpret=(mode == "interpret"))


ssd_step = _ref.ssd_step   # single-token decode: pure jnp everywhere


# --------------------------------------------------------------------------
# Fused quantize-compress (comms wire format)
# --------------------------------------------------------------------------

def _gate_quantize(op: str, n: int) -> "_roofline.GateDecision":
    # Reference composition: flatten writes the fp32 bucket (4n), the
    # absmax pass re-reads it (4n), the quantize pass re-reads it (4n)
    # and writes int8 (n).  Fused-into-flatten: the two kernel phases
    # read the leaves' 4n twice and write int8 once — the intermediate
    # fp32 bucket round trip disappears.
    return _roofline.gate(op, flops=4.0 * n,
                          bytes_ref=13 * n, bytes_fused=9 * n)


def quantize_compress(x) -> Tuple[jax.Array, jax.Array]:
    """(q int8, scale) of ``x`` — fused absmax+cast when the gate says
    the single-kernel form pays, else the two-pass reference."""
    mode = resolve("quantize_compress")
    if _record(_gate_quantize("quantize_compress", x.size), mode):
        return _fused.quantize_compress(x, interpret=(mode == "interpret"))
    return _ref.quantize_compress(x)


def quantize_int8(x, scale) -> jax.Array:
    """Cast against a precomputed (group-agreed) scale — the post-pmax
    half of the comms int8 wire format."""
    mode = resolve("quantize_int8")
    if _record(_gate_quantize("quantize_int8", x.size), mode):
        return _fused.quantize_int8(x, scale,
                                    interpret=(mode == "interpret"))
    return _ref.quantize_int8(x, scale)


quantize_int8_per_channel = _ref.quantize_int8_per_channel  # offline prep


# --------------------------------------------------------------------------
# Paged-attention decode (serving engine)
# --------------------------------------------------------------------------

def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens,
                           layer, *, scale=None):
    """Decode attention of one layer of the stacked ``(L, P, page,
    Hkv*hd)`` pool (see :mod:`repro.kernels.paged_attention`)."""
    B, Hq, hd = q.shape
    page = k_pages.shape[2]
    Hkv = k_pages.shape[3] // hd
    n_pages = block_table.shape[1]
    mode = resolve("paged_decode_attention")
    T = n_pages * page
    kv_elt = jnp.dtype(k_pages.dtype).itemsize
    q_bytes = q.size * jnp.dtype(q.dtype).itemsize
    kv_bytes = 2 * B * T * Hkv * hd * kv_elt
    # reference materializes fp32 scores + probs (write + re-read each)
    scores = 4 * B * Hq * T * 4
    d = _roofline.gate("paged_decode_attention",
                       flops=4.0 * B * Hq * T * hd,
                       bytes_ref=kv_bytes + 2 * q_bytes + scores,
                       bytes_fused=kv_bytes + 2 * q_bytes)
    if _record(d, mode):
        return _paged.paged_decode_attention(
            q, k_pages, v_pages, block_table, seq_lens, layer, scale=scale,
            interpret=(mode == "interpret"))
    return _ref.paged_decode_attention(q, k_pages, v_pages, block_table,
                                       seq_lens, layer, scale=scale)


def paged_decode_latent_attention(q, c_pages, pe_pages, block_table,
                                  seq_lens, layer, *, scale):
    """Latent (MLA) decode attention of one layer of the stacked latent
    pool (see
    :func:`repro.kernels.paged_attention.paged_decode_latent_attention`):
    the Pallas kernel wherever Pallas runs, the oracle elsewhere."""
    mode = resolve("paged_decode_latent_attention")
    if mode in ("pallas", "interpret"):
        return _paged.paged_decode_latent_attention(
            q, c_pages, pe_pages, block_table, seq_lens, layer, scale=scale,
            interpret=(mode == "interpret"))
    return _ref.paged_decode_latent_attention(
        q, c_pages, pe_pages, block_table, seq_lens, layer, scale=scale)


# --------------------------------------------------------------------------
# Dequant-fused GEMM epilogue
# --------------------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def matmul_dequant(a, b_q, b_scale, out_dtype=None, *,
                   bm=256, bn=256, bk=512):
    """C = (A @ B_q) * scale with the dequant fused into the GEMM epilogue.

    Memory-bound shapes (decode-time skinny M) route to the Pallas kernel;
    compute-bound shapes keep XLA's composition (the GEMM dominates and
    the 2*K*N dequant bytes are noise there) — the roofline gate decides.
    Pads non-tiled shapes with zeros (scale padding is irrelevant: the
    padded output columns are sliced away).
    """
    M, K = a.shape
    _, N = b_q.shape
    mode = resolve("matmul_dequant")
    elt = jnp.dtype(a.dtype).itemsize
    out_elt = jnp.dtype(out_dtype or a.dtype).itemsize
    base = M * K * elt + K * N + N * 4 + M * N * out_elt
    d = _roofline.gate("matmul_dequant", flops=2.0 * M * N * K,
                       bytes_ref=base + 2 * K * N * elt,
                       bytes_fused=base)
    if _record(d, mode):
        interp = (mode == "interpret")
        Mp = _round_up(M, bm if M > bm else 8)
        Np = _round_up(N, bn if N > bn else 128)
        Kp = _round_up(K, bk if K > bk else 128)
        if (Mp, Kp, Np) != (M, K, N):
            a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
            b_q = jnp.pad(b_q, ((0, Kp - K), (0, Np - N)))
            b_scale = jnp.pad(b_scale, (0, Np - N))
        out = _gemm.matmul_dequant(
            a, b_q, b_scale, bm=min(bm, Mp), bn=min(bn, Np),
            bk=min(bk, Kp), out_dtype=out_dtype, interpret=interp)
        return out[:M, :N]
    return _ref.matmul_dequant(a, b_q, b_scale, out_dtype)
