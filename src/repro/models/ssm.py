"""Mamba2 (SSD) block with head-parallel TP.

The SSD heads shard over "model" exactly like attention heads; B/C are
per-group (small) and computed replicated.  The scan itself is local per
head — zero collectives inside the recurrence, one reduce for the output
row-parallel projection.  ``ssd_chunked`` is the production pure-JAX path
(16-step chunk scan, compile-friendly); the Pallas kernel replaces it on
TPU via kernels/ops.py.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import manual, precision
from repro.core.layout import Layout, constrain
from repro.core.planner import ParallelPlan
from repro.models import layers
from repro.models.params import ParamSpec


# --------------------------------------------------------------------------
# chunked SSD in pure JAX (same math as kernels/ssd_scan.py)
# --------------------------------------------------------------------------

def ssd_chunked(
    x: jax.Array,                 # (B, S, H, P)
    dt: jax.Array,                # (B, S, H)
    A: jax.Array,                 # (H,)
    Bm: jax.Array,                # (B, S, G, N)
    C: jax.Array,                 # (B, S, G, N)
    *,
    chunk: int = 256,
    init_state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    B, S, H, P = x.shape
    _, _, G, N = Bm.shape
    rep = H // G
    chunk = min(chunk, S)
    # ragged tails pad with dt=0: exp(0)=1 decay and zero input make the
    # padded steps an identity on the state; padded y rows are sliced off
    s_valid = S
    S_pad = (S + chunk - 1) // chunk * chunk
    if S_pad != S:
        pad = ((0, 0), (0, S_pad - S))
        x = jnp.pad(x, pad + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, pad + ((0, 0),))
        Bm = jnp.pad(Bm, pad + ((0, 0), (0, 0)))
        C = jnp.pad(C, pad + ((0, 0), (0, 0)))
        S = S_pad
    nc = S // chunk

    xf = x.astype(jnp.float32).reshape(B, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(B, nc, chunk, H)
    Af = A.astype(jnp.float32)
    Bf = jnp.repeat(Bm.astype(jnp.float32), rep, 2).reshape(B, nc, chunk, H, N)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, 2).reshape(B, nc, chunk, H, N)

    dtA = dtf * Af                                            # (B,nc,Q,H)
    a_cum = jnp.cumsum(dtA, axis=2)
    a_tot = a_cum[:, :, -1, :]                                # (B,nc,H)

    # intra-chunk (the "attention-like" dual form)
    diff = a_cum[:, :, :, None, :] - a_cum[:, :, None, :, :]  # (B,nc,Q,K,H)
    ii = jnp.arange(chunk)
    L = jnp.where((ii[:, None] >= ii[None, :])[None, None, :, :, None],
                  jnp.exp(diff), 0.0)
    scores = jnp.einsum("bcqhn,bckhn->bcqkh", Cf, Bf) * L
    xdt = xf * dtf[..., None]
    y_diag = jnp.einsum("bcqkh,bckhp->bcqhp", scores, xdt)

    # chunk boundary states
    b_decay = Bf * jnp.exp(a_tot[:, :, None, :] - a_cum)[..., None]
    states = jnp.einsum("bckhn,bckhp->bchpn", b_decay, xdt)   # (B,nc,H,P,N)

    # inter-chunk recurrence (nc steps)
    h0 = (jnp.zeros((B, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(h, inp):
        st, at = inp                                          # (B,H,P,N) (B,H)
        h_next = jnp.exp(at)[..., None, None] * h + st
        return h_next, h                                      # emit h_in

    hT, h_in = jax.lax.scan(
        step, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(a_tot, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                           # (B,nc,H,P,N)

    y_off = jnp.einsum("bcqhn,bchpn->bcqhp",
                       Cf * jnp.exp(a_cum)[..., None], h_in)
    y = (y_diag + y_off).reshape(B, S, H, P).astype(x.dtype)
    if S != s_valid:
        y = y[:, :s_valid]
    return y, hT


# --------------------------------------------------------------------------
# the block
# --------------------------------------------------------------------------

def ssm_specs(cfg, plan: ParallelPlan, mesh) -> Dict[str, ParamSpec]:
    D, di = cfg.d_model, cfg.d_inner
    H, G, N, W = cfg.n_ssm_heads, cfg.ssm_groups, cfg.ssm_state, cfg.conv_width
    out_scale = 0.02 / max(1, 2 * cfg.n_layers) ** 0.5
    return {
        "wx": ParamSpec((D, di), plan.ffn_in((D, di), mesh)),
        "wz": ParamSpec((D, di), plan.ffn_in((D, di), mesh)),
        "wbc": ParamSpec((D, 2 * G * N), plan.router((D, 2 * G * N), mesh)),
        "wdt": ParamSpec((D, H), plan.router((D, H), mesh)),
        "dt_bias": ParamSpec((H,), plan.head_vector((H,), mesh),
                             dtype=jnp.float32, init="dt_bias"),
        "A": ParamSpec((H,), plan.head_vector((H,), mesh),
                       dtype=jnp.float32, init="ssm_a"),
        "D_skip": ParamSpec((H,), plan.head_vector((H,), mesh),
                            dtype=jnp.float32, init="ones"),
        "conv_x": ParamSpec((W, di), plan.conv1d((W, di), mesh),
                            init="normal", scale=0.5 / W),
        "conv_bc": ParamSpec((W, 2 * G * N), Layout((None, None)),
                             init="normal", scale=0.5 / W),
        "gate_norm": ParamSpec((di,), Layout((None,)), init="ones"),
        "w_out": ParamSpec((di, D), plan.ffn_out((di, D), mesh),
                           init="scaled", scale=out_scale),
    }


def _causal_conv(u: jax.Array, w: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv along S.  u: (B,S,C), w: (W,C).

    Returns (out, new_state) where state is the last W-1 inputs (decode).
    """
    Wd = w.shape[0]
    if state is None:
        pad = jnp.zeros((u.shape[0], Wd - 1, u.shape[2]), u.dtype)
    else:
        pad = state.astype(u.dtype)
    ext = jnp.concatenate([pad, u], axis=1)                   # (B, S+W-1, C)
    out = sum(ext[:, i:i + u.shape[1], :] * w[i][None, None, :]
              for i in range(Wd))
    new_state = ext[:, -(Wd - 1):, :] if Wd > 1 else None
    return out.astype(u.dtype), new_state


def forward_shardmap(
    x: jax.Array,                 # (B, S, D) seq-sharded bf16
    p: dict,
    cfg,
    plan: ParallelPlan,
    mesh,
    *,
    policy,
    ssd_chunk: int = 256,
    with_state: bool = False,
):
    """Mamba2 mixer with EXPLICIT bf16 collectives (shard_map over TP).

    AG the seq-sharded residual once (bf16), everything else is local to
    the head shard (projections, conv, SSD scan), the gated RMSNorm does
    one tiny psum of sum-of-squares, and the output reduce-scatters back
    (bf16).  Replaces fp32 GSPMD boundary collectives (§Perf iter 5).
    """
    from jax.sharding import PartitionSpec as P
    tp = plan.tp_axis
    B, S, D = x.shape
    H, Pd = cfg.n_ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    di = cfg.d_inner
    eps = cfg.norm_eps

    specs = {
        "wx": P(None, tp), "wz": P(None, tp), "wbc": P(None, None),
        "wdt": P(None, tp), "dt_bias": P(tp), "A": P(tp), "D_skip": P(tp),
        "conv_x": P(None, tp), "conv_bc": P(None, None),
        "gate_norm": P(tp), "w_out": P(tp, None),
    }

    def body(xl, pl):
        xg = jax.lax.all_gather(xl, tp, axis=1, tiled=True)    # bf16 wire
        xz = precision.einsum("bsd,de->bse", xg, pl["wx"], policy=policy)
        z = precision.einsum("bsd,de->bse", xg, pl["wz"], policy=policy)
        bc = precision.einsum("bsd,de->bse", xg, pl["wbc"], policy=policy)
        dt = jax.nn.softplus(
            precision.einsum("bsd,dh->bsh", xg, pl["wdt"], policy=policy
                             ).astype(jnp.float32)
            + pl["dt_bias"].astype(jnp.float32))

        xz, conv_new = _causal_conv(xz.astype(xg.dtype),
                                    pl["conv_x"].astype(xg.dtype), None)
        xz = jax.nn.silu(xz)
        bc, bc_new = _causal_conv(bc.astype(xg.dtype),
                                  pl["conv_bc"].astype(xg.dtype), None)
        bc = jax.nn.silu(bc)

        b, s = xg.shape[0], xg.shape[1]      # LOCAL batch, full seq
        h_loc = xz.shape[-1] // Pd
        xh = xz.reshape(b, s, h_loc, Pd)
        Bm = bc[..., :G * N].reshape(b, s, G, N)
        Cm = bc[..., G * N:].reshape(b, s, G, N)
        y, state = ssd_chunked(xh, dt, pl["A"].astype(jnp.float32),
                               Bm, Cm, chunk=ssd_chunk)
        y = y + xh * pl["D_skip"].astype(jnp.float32)[
            None, None, :, None].astype(y.dtype)
        y = y.reshape(b, s, xz.shape[-1])

        # gated RMSNorm over the FULL d_inner (one small psum)
        v = (y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
             ).astype(jnp.float32)
        ss = jax.lax.psum(jnp.sum(v * v, -1, keepdims=True), tp) / di
        v = (v * jax.lax.rsqrt(ss + eps)
             * pl["gate_norm"].astype(jnp.float32)).astype(xg.dtype)

        out = precision.einsum("bse,ed->bsd", v, pl["w_out"], policy=policy)
        out = jax.lax.psum_scatter(out.astype(xl.dtype), tp,
                                   scatter_dimension=1, tiled=True)
        return out, conv_new, state, bc_new

    ba = plan.batch_axes
    out, conv_new, state, bc_new = manual.shard_map(
        body, mesh=mesh,
        in_specs=(P(ba, tp, None), {k: specs[k] for k in p}),
        out_specs=(P(ba, tp, None), P(ba, None, tp),
                   P(ba, tp, None, None), P(ba, None, None)),
    )(x, dict(p))
    if with_state:
        return out, (conv_new, state, bc_new)
    return out, None


def forward(
    x: jax.Array,                 # (B, S, D)
    p: dict,
    cfg,
    plan: ParallelPlan,
    *,
    policy,
    ssd_chunk: int = 256,
    conv_state: Optional[jax.Array] = None,
    ssm_state: Optional[jax.Array] = None,
    with_state: bool = False,
):
    """Full-sequence Mamba2 mixer.  Returns (y, (conv_state, ssd_state))."""
    B, S, D = x.shape
    H, P = cfg.n_ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state

    if plan.seq_parallel_residual:
        # gather the bf16 residual to full sequence (the conv + scan need
        # contiguous S); output reduce-scatters back
        x = constrain(x, Layout((plan.batch_axes, None, None)))
    act_l = Layout((plan.batch_axes, None, plan.tp_axis))
    xz = precision.einsum("bsd,de->bse", x, p["wx"], policy=policy)
    z = precision.einsum("bsd,de->bse", x, p["wz"], policy=policy)
    xz = constrain(xz, act_l)
    z = constrain(z, act_l)
    bc = precision.einsum("bsd,de->bse", x, p["wbc"], policy=policy)
    dt_raw = precision.einsum("bsd,dh->bsh", x, p["wdt"], policy=policy)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))

    xz, conv_new = _causal_conv(xz, p["conv_x"].astype(xz.dtype),
                                conv_state)
    xz = jax.nn.silu(xz)
    bc, bc_conv_new = _causal_conv(bc, p["conv_bc"].astype(bc.dtype), None)
    bc = jax.nn.silu(bc)

    xh = xz.reshape(B, S, H, P)
    xh = constrain(xh, Layout((plan.batch_axes, None, plan.tp_axis, None)))
    Bm = bc[..., :G * N].reshape(B, S, G, N)
    Cm = bc[..., G * N:].reshape(B, S, G, N)

    y, state = ssd_chunked(xh, dt, p["A"], Bm, Cm, chunk=ssd_chunk,
                           init_state=ssm_state)
    y = y + xh * p["D_skip"].astype(jnp.float32)[None, None, :, None
                                                 ].astype(y.dtype)
    y = y.reshape(B, S, cfg.d_inner)
    # gated RMSNorm (mamba2): norm(y * silu(z))
    y = layers.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                        p["gate_norm"], cfg.norm_eps)
    out = precision.einsum("bse,ed->bsd", y, p["w_out"], policy=policy)
    out = constrain(out.astype(x.dtype), plan.hidden())
    if with_state:
        return out, (conv_new, state, bc_conv_new)
    return out, None


def decode_step(
    x: jax.Array,                 # (B, 1, D)
    p: dict,
    cfg,
    plan: ParallelPlan,
    conv_state: jax.Array,        # (B, W-1, d_inner)
    ssm_state: jax.Array,         # (B, H, P, N)
    bc_conv_state: jax.Array,     # (B, W-1, 2GN)
    *,
    policy,
):
    """Single-token SSD recurrence step (serving)."""
    from repro.kernels import ops as kops
    B, _, D = x.shape
    H, P = cfg.n_ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state

    xz = precision.einsum("bsd,de->bse", x, p["wx"], policy=policy)
    z = precision.einsum("bsd,de->bse", x, p["wz"], policy=policy)
    bc = precision.einsum("bsd,de->bse", x, p["wbc"], policy=policy)
    dt_raw = precision.einsum("bsd,dh->bsh", x, p["wdt"], policy=policy)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))[:, 0]  # (B,H)

    # rolling conv states
    ext = jnp.concatenate([conv_state.astype(xz.dtype), xz], axis=1)
    w = p["conv_x"].astype(xz.dtype)
    xz1 = sum(ext[:, i:i + 1, :] * w[i][None, None, :]
              for i in range(w.shape[0]))
    conv_state = ext[:, 1:, :]
    ext_bc = jnp.concatenate([bc_conv_state.astype(bc.dtype), bc], axis=1)
    wbc = p["conv_bc"].astype(bc.dtype)
    bc1 = sum(ext_bc[:, i:i + 1, :] * wbc[i][None, None, :]
              for i in range(wbc.shape[0]))
    bc_conv_state = ext_bc[:, 1:, :]

    xz1 = jax.nn.silu(xz1)
    bc1 = jax.nn.silu(bc1)
    xh = xz1.reshape(B, H, P)
    Bm = bc1[:, 0, :G * N].reshape(B, G, N)
    Cm = bc1[:, 0, G * N:].reshape(B, G, N)

    y, ssm_state = kops.ssd_step(xh, dt, p["A"].astype(jnp.float32),
                                 Bm, Cm, ssm_state)
    y = y + xh * p["D_skip"].astype(jnp.float32)[None, :, None].astype(y.dtype)
    y = y.reshape(B, 1, cfg.d_inner)
    y = layers.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                        p["gate_norm"], cfg.norm_eps)
    out = precision.einsum("bse,ed->bsd", y, p["w_out"], policy=policy)
    return out.astype(x.dtype), conv_state, ssm_state, bc_conv_state
