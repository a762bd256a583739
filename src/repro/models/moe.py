"""Mixture-of-Experts with replicated-routing expert parallelism.

dMath predates MoE, but EP *is* its layout-independence story: the expert
bank is a distributed (E, D, F) tensor row-blocked over the "model" axis,
and token dispatch is a redistribution handled the same way the GEMM
remapping service handles incompatible layouts (DESIGN §5).

The dispatch algorithm (shard_map over the full mesh):

  1. every model shard routes the *full* local token block (router weights
     are replicated — routing is deterministic and identical everywhere, so
     no metadata broadcast is needed: paper §2.3's distributed seeds / §3.3
     cached plans),
  2. each shard selects the tokens whose top-k choices land on one of ITS
     E/tp experts, packs them into a (E_loc, C, D) capacity buffer
     (sort-free ranking via a one-hot cumsum),
  3. local expert FFN (three MXU matmuls),
  4. combine: scatter back weighted outputs, then one psum over "model" —
     the same wire cost as a row-parallel dense FFN, with NO all-to-all.

Capacity C = ceil(T_local * top_k / E * capacity_factor); overflow tokens
drop (their combine weight is 0) — GShard-style, the load-balancing aux
loss keeps drops rare.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import manual, precision
from repro.core.layout import Layout
from repro.core.planner import ParallelPlan
from repro.models import layers
from repro.models.params import ParamSpec


def moe_specs(cfg, plan: ParallelPlan, mesh) -> Dict[str, ParamSpec]:
    # the router scores all n_experts; the bank holds this chip's share
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    Eh = cfg.n_held_experts
    s = {
        "router": ParamSpec((D, E), plan.router((D, E), mesh),
                            dtype=jnp.float32),
        "w_gate": ParamSpec((Eh, D, Fe), plan.experts((Eh, D, Fe), mesh)),
        "w_in": ParamSpec((Eh, D, Fe), plan.experts((Eh, D, Fe), mesh)),
        "w_out": ParamSpec((Eh, Fe, D), plan.experts((Eh, Fe, D), mesh),
                           init="scaled",
                           scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5),
    }
    if cfg.n_shared_experts:
        Fs = cfg.d_shared_ff
        s["shared_gate"] = ParamSpec((D, Fs), plan.ffn_in((D, Fs), mesh))
        s["shared_in"] = ParamSpec((D, Fs), plan.ffn_in((D, Fs), mesh))
        s["shared_out"] = ParamSpec((Fs, D), plan.ffn_out((Fs, D), mesh),
                                    init="scaled",
                                    scale=0.02 / max(1, 2 * cfg.n_layers) ** 0.5)
    return s


def forward(
    x: jax.Array,                 # (B, S, D) hidden, NOT seq-sharded
    p: dict,
    cfg,
    plan: ParallelPlan,
    mesh,
    *,
    policy,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss).  aux is the switch-style load-balance loss."""
    B, S, D = x.shape
    tp = plan.tp_axis
    tp_n = mesh.shape[tp]
    E, K = cfg.n_experts, cfg.top_k
    e_loc = E // tp_n
    # local tokens per (pod, data) shard
    import math
    nb = math.prod(mesh.shape[a] for a in plan.batch_axes)
    t_loc = (B // nb) * S
    cap = int(math.ceil(t_loc * K / E * cfg.capacity_factor))
    cap = max(cap, 8)

    x_spec = Layout((plan.batch_axes, None, None)).spec
    rep2 = Layout.replicated(2).spec
    exp_spec = Layout((tp, None, None)).spec
    # combine via reduce-scatter onto the seq-sharded residual when the
    # sequence divides the axis (train/prefill); decode (S=1) falls back
    # to the full psum
    scatter_seq = plan.seq_parallel_residual and S % tp_n == 0 and S >= tp_n
    out_spec = (Layout((plan.batch_axes, tp, None)).spec if scatter_seq
                else x_spec)

    def body(xl, router_w, w_gate, w_in, w_out):
        bl, sl, _ = xl.shape
        t = xl.reshape(bl * sl, D)
        T = t.shape[0]

        # -- routing (identical on every model shard) ---------------------
        logits = (t.astype(jnp.float32) @ router_w)             # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)           # (T, K)
        if cfg.norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

        # aux loss: mean prob per expert * fraction routed per expert
        frac = jnp.mean(
            jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), 1), 0)
        aux = E * jnp.sum(jnp.mean(probs, 0) * frac)

        # -- capacity ranking (sort-free, deterministic) -------------------
        flat_e = gate_idx.reshape(-1)                           # (T*K,)
        flat_w = gate_vals.reshape(-1)
        tok_id = jnp.repeat(jnp.arange(T), K)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)     # (T*K, E)
        pos = jnp.cumsum(onehot, axis=0) - 1                    # rank in expert
        rank = jnp.take_along_axis(pos, flat_e[:, None], 1)[:, 0]

        shard = jax.lax.axis_index(tp)
        local_e = flat_e - shard * e_loc
        keep = ((local_e >= 0) & (local_e < e_loc) & (rank < cap))
        dst = jnp.where(keep, local_e * cap + rank, e_loc * cap)  # sentinel

        buf = jnp.zeros((e_loc * cap + 1, D), xl.dtype)
        buf = buf.at[dst].set(jnp.where(keep[:, None], t[tok_id], 0),
                              mode="drop")
        eb = buf[:-1].reshape(e_loc, cap, D)

        # -- expert FFN (local, MXU) ---------------------------------------
        g = precision.einsum("ecd,edf->ecf", eb, w_gate, policy=policy)
        h = precision.einsum("ecd,edf->ecf", eb, w_in, policy=policy)
        h = layers.act_fn(cfg.act)(g) * h
        yb = precision.einsum("ecf,efd->ecd", h.astype(eb.dtype), w_out,
                              policy=policy)                    # (e_loc,C,D)

        # -- combine --------------------------------------------------------
        # the (token, k) slots are dense in flat order, so the inverse of
        # the dispatch scatter is a gather + reshape + sum over k — no
        # scatter (a scatter here materializes a (T*K, D) u32 index
        # broadcast; measured +1.1 GiB on dbrx train_4k)
        flat_y = yb.reshape(e_loc * cap, D)
        picked = jnp.take(flat_y, jnp.clip(dst, 0, e_loc * cap - 1), axis=0)
        w_eff = (flat_w * keep).astype(jnp.float32)
        y = jnp.sum(picked.reshape(T, K, D).astype(jnp.float32)
                    * w_eff.reshape(T, K, 1), axis=1)
        # combine across expert shards on the bf16 wire (paper §4.2's
        # reduced-precision transfers); reduce-scatter straight onto the
        # seq-sharded residual when possible (1/tp of the psum bytes)
        y = y.astype(xl.dtype).reshape(bl, sl, D)
        if scatter_seq:
            y = jax.lax.psum_scatter(y, tp, scatter_dimension=1, tiled=True)
        else:
            y = jax.lax.psum(y, tp)
        # aux is identical on every model shard (same routing); average it
        # over the batch shards only.
        aux = jax.lax.pmean(aux, plan.batch_axes)
        return y, aux

    y, aux = manual.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, rep2, exp_spec, exp_spec, exp_spec),
        out_specs=(out_spec, jax.sharding.PartitionSpec()),
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])

    if cfg.n_shared_experts:
        shared = layers.glu_mlp(
            x, p["shared_gate"], p["shared_in"], p["shared_out"],
            act=cfg.act, policy=policy,
            h_layout=Layout((plan.batch_axes, None, plan.tp_axis)))
        y = y + shared
    return y, aux


def forward_held(x: jax.Array, p: dict, cfg, *, policy
                 ) -> Tuple[jax.Array, jax.Array]:
    """Dropless expert layer of one chip's share (``cfg.expert_shard``).

    Routes every token over all ``n_experts`` (softmax gate, top-k, the
    weights renormalised only when ``cfg.norm_topk_prob``) and computes
    the part of the result that the held experts give, plus the shared
    experts.  Each held expert takes all T tokens of the step, weighted by
    the gate (0 where the token did not choose it): no capacity, no drops,
    and the same work whatever the routing.  What the other chips' experts
    would add is left out; on one chip there is no exchange.

    Returns (y (B, S, D), tokens routed to each held expert (n_held,)
    int32)."""
    B, S, D = x.shape
    t = x.reshape(B * S, D)
    probs = jax.nn.softmax(t.astype(jnp.float32) @ p["router"], axis=-1)
    vals, idx = jax.lax.top_k(probs, cfg.top_k)                 # (T, K)
    if cfg.norm_topk_prob:
        vals = vals / jnp.maximum(jnp.sum(vals, -1, keepdims=True), 1e-9)
    held = cfg.held_first + jnp.arange(cfg.n_held_experts)
    chose = idx[:, :, None] == held                             # (T, K, Eh)
    w = jnp.sum(jnp.where(chose, vals[:, :, None], 0.0), axis=1)  # (T, Eh)
    counts = jnp.sum(chose, axis=(0, 1), dtype=jnp.int32)

    g = precision.einsum("td,edf->etf", t, p["w_gate"], policy=policy)
    h = precision.einsum("td,edf->etf", t, p["w_in"], policy=policy)
    h = layers.act_fn(cfg.act)(g) * h * w.T[:, :, None]
    y = precision.einsum("etf,efd->td", h.astype(x.dtype), p["w_out"],
                         policy=policy).reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + layers.glu_mlp(x, p["shared_gate"], p["shared_in"],
                               p["shared_out"], act=cfg.act, policy=policy)
    return y.astype(x.dtype), counts
