"""The model zoo assembled on the dMath substrate.

One :class:`Model` serves all six families (dense / moe / ssm / hybrid /
audio / vlm).  Layers are *stacked* (leading L dim) and applied with
``lax.scan`` so the traced HLO is one layer body — the §3.3 "workers
remember the entire forward computation" trick is the scan itself: metadata
(= jaxpr) is O(1) in depth, not O(L).

Entry points
  ``loss_fn``      (B,S) tokens -> scalar loss        (train_* shapes)
  ``prefill``      (B,S) tokens -> logits, kv-cache   (prefill_* shapes)
  ``decode_step``  one token + cache -> logits, cache (decode_* / long_*)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import precision
from repro.core.layout import Layout, constrain
from repro.core.planner import ParallelPlan, plan_for
from repro.models import attention, layers, mla, moe, ssm
from repro.models.params import (ParamSpec, tree_init, tree_layouts,
                                 tree_sds, tree_shardings)

NEG = -1e30


@dataclasses.dataclass
class Model:
    cfg: Any
    mesh: Any
    plan: Optional[ParallelPlan] = None
    policy: Any = precision.MIXED
    remat: str = "full"             # full | none
    q_chunk: int = 512
    kv_chunk: int = 1024
    ssd_chunk: int = 256

    def __post_init__(self):
        if self.plan is None:
            self.plan = plan_for(self.cfg, self.mesh)

    # ------------------------------------------------------------------
    # parameter specs
    # ------------------------------------------------------------------
    def _layer_specs(self, dense: bool = False) -> Dict[str, Any]:
        """One layer's specs; ``dense`` forces the gated MLP of width
        ``d_ff`` (the leading dense layers of an MoE stack)."""
        cfg, plan, mesh = self.cfg, self.plan, self.mesh
        D = cfg.d_model
        out_scale = 0.02 / max(1, 2 * cfg.n_layers) ** 0.5
        s: Dict[str, Any] = {}
        if cfg.family in ("dense", "moe", "audio", "vlm"):
            s["ln1"] = ParamSpec((D,), plan.vector((D,), mesh), init="ones")
            s["ln2"] = ParamSpec((D,), plan.vector((D,), mesh), init="ones")
            s["attn"] = (mla.mla_specs(cfg, plan, mesh) if cfg.is_mla
                         else attention.attn_specs(cfg, plan, mesh))
            if cfg.family == "moe" and not dense:
                s["moe"] = moe.moe_specs(cfg, plan, mesh)
            else:
                F = cfg.d_ff
                s["mlp"] = {
                    "gate": ParamSpec((D, F), plan.ffn_in((D, F), mesh)),
                    "in": ParamSpec((D, F), plan.ffn_in((D, F), mesh)),
                    "out": ParamSpec((F, D), plan.ffn_out((F, D), mesh),
                                     init="scaled", scale=out_scale),
                }
        elif cfg.family in ("ssm", "hybrid"):
            s["ln1"] = ParamSpec((D,), plan.vector((D,), mesh), init="ones")
            s["ssm"] = ssm.ssm_specs(cfg, plan, mesh)
        return s

    def param_specs(self) -> Dict[str, Any]:
        cfg, plan, mesh = self.cfg, self.plan, self.mesh
        D, V = cfg.d_model, cfg.padded_vocab
        specs: Dict[str, Any] = {
            "embed": ParamSpec((V, D), plan.embed((V, D), mesh), scale=0.02),
            "unembed": ParamSpec((D, V), plan.unembed((D, V), mesh)),
            "final_norm": ParamSpec((D,), plan.vector((D,), mesh),
                                    init="ones"),
        }
        stack = lambda tree, n: jax.tree.map(
            lambda sp: sp.stacked(n), tree,
            is_leaf=lambda x: isinstance(x, ParamSpec))
        k = cfg.first_k_dense
        specs["layers"] = stack(self._layer_specs(), cfg.n_layers - k)
        if k:
            specs["dense_layers"] = stack(self._layer_specs(dense=True), k)
        if cfg.family == "hybrid":
            # the zamba2 shared transformer block (one set of weights,
            # applied every cfg.attn_every layers)
            F = cfg.d_ff
            out_scale = 0.02 / max(1, 2 * cfg.n_layers) ** 0.5
            specs["shared"] = {
                "ln1": ParamSpec((D,), plan.vector((D,), mesh), init="ones"),
                "ln2": ParamSpec((D,), plan.vector((D,), mesh), init="ones"),
                "attn": attention.attn_specs(cfg, plan, mesh),
                "mlp": {
                    "gate": ParamSpec((D, F), plan.ffn_in((D, F), mesh)),
                    "in": ParamSpec((D, F), plan.ffn_in((D, F), mesh)),
                    "out": ParamSpec((F, D), plan.ffn_out((F, D), mesh),
                                     init="scaled", scale=out_scale),
                },
            }
        return specs

    def init(self, key: jax.Array):
        return tree_init(key, self.param_specs())

    def param_sds(self):
        return tree_sds(self.param_specs())

    def param_shardings(self):
        return tree_shardings(self.param_specs(), self.mesh)

    def param_layouts(self):
        return tree_layouts(self.param_specs())

    # ------------------------------------------------------------------
    # per-layer static flags (gemma3 local/global windows, zamba2 sites)
    # ------------------------------------------------------------------
    def _window_array(self, seq_len: int) -> Optional[jax.Array]:
        cfg = self.cfg
        if cfg.window is None:
            return None
        wins = [seq_len + 1 if cfg.is_global_layer(i) else cfg.window
                for i in range(cfg.n_layers)]
        return jnp.asarray(wins, jnp.int32)

    # ------------------------------------------------------------------
    # layer bodies
    # ------------------------------------------------------------------
    def _dense_block(self, x, lp, window, with_cache: bool):
        cfg, plan = self.cfg, self.plan
        h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, cache = attention.forward(
            h, lp["attn"], cfg, plan, self.mesh, policy=self.policy,
            window=window, q_chunk=self.q_chunk, kv_chunk=self.kv_chunk,
            with_cache=with_cache)
        x = constrain(x + a, plan.hidden())
        h = layers.rms_norm(x, lp["ln2"], cfg.norm_eps)
        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "moe":
            f, aux = moe.forward(h, lp["moe"], cfg, plan, self.mesh,
                                 policy=self.policy)
        elif plan.ffn_replicated:
            # fully local over the sequence shards: no collectives at all
            f = layers.glu_mlp(
                h, lp["mlp"]["gate"], lp["mlp"]["in"], lp["mlp"]["out"],
                act=cfg.act, policy=self.policy)
        elif plan.seq_parallel_residual:
            # explicit bf16 AG -> TP -> bf16 RS (shard_map)
            f = layers.glu_mlp_shardmap(
                h, lp["mlp"]["gate"], lp["mlp"]["in"], lp["mlp"]["out"],
                act=cfg.act, mesh=self.mesh, plan=plan, policy=self.policy)
        else:
            f = layers.glu_mlp(
                h, lp["mlp"]["gate"], lp["mlp"]["in"], lp["mlp"]["out"],
                act=cfg.act, policy=self.policy,
                h_layout=Layout((plan.batch_axes, None, plan.tp_axis)),
                gather_layout=(Layout((plan.batch_axes, None, None))
                               if plan.seq_parallel_residual else None),
                out_layout=plan.hidden())
        x = constrain(x + f, plan.hidden())
        return x, aux, cache

    def _ssm_block(self, x, lp, with_state: bool):
        cfg, plan = self.cfg, self.plan
        h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
        if plan.seq_parallel_residual:
            y, state = ssm.forward_shardmap(
                h, lp["ssm"], cfg, plan, self.mesh, policy=self.policy,
                ssd_chunk=self.ssd_chunk, with_state=with_state)
        else:
            y, state = ssm.forward(h, lp["ssm"], cfg, plan,
                                   policy=self.policy,
                                   ssd_chunk=self.ssd_chunk,
                                   with_state=with_state)
        x = constrain(x + y, plan.hidden())
        return x, state

    def _shared_block(self, x, sp, window, with_cache: bool):
        """zamba2 shared attention+MLP block (weights reused per site)."""
        cfg, plan = self.cfg, self.plan
        h = layers.rms_norm(x, sp["ln1"], cfg.norm_eps)
        a, cache = attention.forward(
            h, sp["attn"], cfg, plan, self.mesh, policy=self.policy,
            window=None, q_chunk=self.q_chunk, kv_chunk=self.kv_chunk,
            with_cache=with_cache)
        x = constrain(x + a, plan.hidden())
        h = layers.rms_norm(x, sp["ln2"], cfg.norm_eps)
        if plan.seq_parallel_residual:
            f = layers.glu_mlp_shardmap(
                h, sp["mlp"]["gate"], sp["mlp"]["in"], sp["mlp"]["out"],
                act=cfg.act, mesh=self.mesh, plan=plan, policy=self.policy)
        else:
            f = layers.glu_mlp(
                h, sp["mlp"]["gate"], sp["mlp"]["in"], sp["mlp"]["out"],
                act=cfg.act, policy=self.policy,
                h_layout=Layout((plan.batch_axes, None, plan.tp_axis)))
        x = constrain(x + f, plan.hidden())
        return x, cache

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------
    def _embed(self, params, tokens, vision_embeds=None):
        cfg, plan = self.cfg, self.plan
        B = tokens.shape[0]
        nb = _nb(self.mesh, plan)
        ba = plan.batch_axes if (B % nb == 0 and B >= nb) else None
        x = layers.embed_shard_map(
            tokens, params["embed"], self.mesh, batch_axes=ba,
            tp_axis=plan.tp_axis, scale=cfg.emb_scale)
        if vision_embeds is not None:
            x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        return constrain(x.astype(jnp.bfloat16),
                         self._maybe_batch(plan.hidden(), B))

    def _maybe_batch(self, layout: Layout, B: int) -> Layout:
        """Drop the batch axes from a layout when B is not shardable
        (long_500k: global_batch=1 < data axis — DESIGN §4)."""
        nb = _nb(self.mesh, self.plan)
        if B % nb == 0 and B >= nb:
            return layout
        return Layout((None,) + layout.dims[1:])

    def _head(self, params, x):
        cfg, plan = self.cfg, self.plan
        B = x.shape[0]
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        x = constrain(x, self._maybe_batch(plan.hidden(seq_sharded=False), B))
        return layers.unembed(x, params["unembed"], policy=self.policy,
                              out_layout=self._maybe_batch(plan.logits(), B))

    # ------------------------------------------------------------------
    # full-sequence forward (train / prefill)
    # ------------------------------------------------------------------
    def forward(self, params, tokens, vision_embeds=None,
                with_cache: bool = False, last_only: bool = False):
        cfg, plan = self.cfg, self.plan
        if cfg.is_mla or cfg.first_k_dense:
            raise NotImplementedError(
                f"{cfg.name}: latent attention and leading dense layers "
                "run on the paged serve path only (prefill_chunk_paged, "
                "decode_step_paged)")
        x = self._embed(params, tokens, vision_embeds)
        B, S, _ = x.shape
        windows = self._window_array(S)

        if cfg.family in ("dense", "moe", "audio", "vlm"):
            def body(carry, xs):
                x, aux = carry
                lp, win = xs
                win = win if windows is not None else None
                x, a, cache = self._dense_block(x, lp, win, with_cache)
                return (x, aux + a), cache

            xs = (params["layers"],
                  windows if windows is not None
                  else jnp.zeros((cfg.n_layers,), jnp.int32))
            carry0 = (x, jnp.zeros((), jnp.float32))
            group = 0
            if self.remat.startswith("group:") and not with_cache:
                group = int(self.remat.split(":")[1])
                if cfg.n_layers % group:
                    group = 0
            if group:
                # sqrt-L double remat: outer saves L/G carries, inner
                # recomputes per layer — carry HBM drops from L to L/G + G
                inner = jax.checkpoint(body)

                def outer(carry, xs_g):
                    carry, _ = jax.lax.scan(inner, carry, xs_g)
                    return carry, None

                xs_g = jax.tree.map(
                    lambda a: a.reshape((cfg.n_layers // group, group)
                                        + a.shape[1:]), xs)
                (x, aux), _ = jax.lax.scan(jax.checkpoint(outer),
                                           carry0, xs_g)
                caches = None
            else:
                step = jax.checkpoint(body) if self.remat == "full" else body
                (x, aux), caches = jax.lax.scan(step, carry0, xs)

        elif cfg.family == "ssm":
            def body(x, lp):
                x, state = self._ssm_block(x, lp, with_cache)
                return x, state
            step = jax.checkpoint(body) if self.remat == "full" else body
            x, caches = jax.lax.scan(step, x, params["layers"])
            aux = jnp.zeros((), jnp.float32)

        else:  # hybrid (zamba2): static 6-layer groups, NO lax.cond —
            # sites are compile-time positions, so the stack splits into
            # n_sites groups of (attn_every mamba layers + shared block)
            # plus a mamba tail.  This keeps the HLO exact for the cost
            # walker and skips the untaken-branch machinery entirely.
            every = cfg.attn_every
            shared = params["shared"]
            n_sites = cfg.n_layers // every
            n_tail = cfg.n_layers - n_sites * every

            head_p = jax.tree.map(lambda a: a[:n_sites * every].reshape(
                (n_sites, every) + a.shape[1:]), params["layers"])
            tail_p = jax.tree.map(lambda a: a[n_sites * every:],
                                  params["layers"])

            def mamba_body(x, lp):
                return self._ssm_block(x, lp, with_cache)

            mamba_step = (jax.checkpoint(mamba_body)
                          if self.remat == "full" else mamba_body)

            def group_body(x, gp):
                x, sstates = jax.lax.scan(mamba_step, x, gp)
                x, cache = self._shared_block(x, shared, None, with_cache)
                return x, (sstates, cache)

            group_step = (jax.checkpoint(group_body)
                          if self.remat == "full" else group_body)
            x, (sstates, site_caches) = jax.lax.scan(group_step, x, head_p)
            tail_states = None
            if n_tail:
                x, tail_states = jax.lax.scan(mamba_step, x, tail_p)
            caches = ((sstates, tail_states), site_caches)
            aux = jnp.zeros((), jnp.float32)

        if last_only:
            x = x[:, -1:, :]
        logits = self._head(params, x)
        return logits, aux, (caches if with_cache else None)

    # ------------------------------------------------------------------
    # training loss
    # ------------------------------------------------------------------
    def loss_fn(self, params, batch):
        cfg = self.cfg
        logits, aux, _ = self.forward(
            params, batch["tokens"], batch.get("vision_embeds"))
        loss, denom = layers.lm_loss(logits, batch["labels"],
                                     vocab_real=cfg.vocab_size)
        if cfg.family == "moe":
            loss = loss + cfg.router_aux_coef * aux / cfg.n_layers
        metrics = {"loss": loss, "aux": aux, "tokens": denom}
        return loss, metrics

    # ------------------------------------------------------------------
    # serving: cache specs / prefill / decode
    # ------------------------------------------------------------------
    def _windowed(self) -> bool:
        """gemma3-style interleaved local/global: local layers keep an
        O(window) ring cache instead of O(seq) — 5x less decode HBM."""
        cfg = self.cfg
        return bool(cfg.window and cfg.local_global_pattern
                    and cfg.family in ("dense", "moe", "audio", "vlm"))

    def cache_specs(self, batch: int, seq_len: int) -> Dict[str, ParamSpec]:
        cfg, plan, mesh = self.cfg, self.plan, self.mesh
        L = cfg.n_layers
        out: Dict[str, ParamSpec] = {}
        if self._windowed():
            W = min(cfg.window, seq_len)
            n_g = sum(cfg.is_global_layer(i) for i in range(L))
            n_l = L - n_g
            lay = plan.kv_cache(batch, mesh)
            gshape = (n_g, batch, seq_len, cfg.n_kv_heads, cfg.d_head)
            lshape = (n_l, batch, W, cfg.n_kv_heads, cfg.d_head)
            llay = lay if Layout(lay.dims).divisible(lshape, mesh) else \
                Layout((None, lay.dims[1], None, None, None))
            out["k_g"] = ParamSpec(gshape, lay, dtype=jnp.bfloat16,
                                   init="zeros")
            out["v_g"] = ParamSpec(gshape, lay, dtype=jnp.bfloat16,
                                   init="zeros")
            out["k_l"] = ParamSpec(lshape, llay, dtype=jnp.bfloat16,
                                   init="zeros")
            out["v_l"] = ParamSpec(lshape, llay, dtype=jnp.bfloat16,
                                   init="zeros")
            return out
        if cfg.family in ("dense", "moe", "audio", "vlm"):
            shape = (L, batch, seq_len, cfg.n_kv_heads, cfg.d_head)
            lay = plan.kv_cache(batch, mesh)
            out["k"] = ParamSpec(shape, lay, dtype=jnp.bfloat16, init="zeros")
            out["v"] = ParamSpec(shape, lay, dtype=jnp.bfloat16, init="zeros")
        if cfg.family in ("ssm", "hybrid"):
            H, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            W, di, GN2 = cfg.conv_width, cfg.d_inner, 2 * cfg.ssm_groups * cfg.ssm_state
            bl = plan.batch_axes if batch >= _nb(mesh, plan) else None
            out["ssm"] = ParamSpec((L, batch, H, P, N),
                                   plan.ssm_state(batch, mesh),
                                   dtype=jnp.float32, init="zeros")
            out["conv"] = ParamSpec(
                (L, batch, W - 1, di),
                Layout((None, bl, None, plan.tp_axis)),
                dtype=jnp.bfloat16, init="zeros")
            out["bc_conv"] = ParamSpec(
                (L, batch, W - 1, GN2),
                Layout((None, bl, None, None)),
                dtype=jnp.bfloat16, init="zeros")
        if cfg.family == "hybrid":
            n_sites = cfg.n_layers // cfg.attn_every
            shape = (n_sites, batch, seq_len, cfg.n_kv_heads, cfg.d_head)
            lay = plan.kv_cache(batch, mesh)
            out["k"] = ParamSpec(shape, lay, dtype=jnp.bfloat16, init="zeros")
            out["v"] = ParamSpec(shape, lay, dtype=jnp.bfloat16, init="zeros")
        return out

    def init_cache(self, batch: int, seq_len: int):
        return tree_init(jax.random.PRNGKey(0),
                         self.cache_specs(batch, seq_len))

    # ------------------------------------------------------------------
    # block-paged KV cache (serving; precursor of continuous batching)
    # ------------------------------------------------------------------
    def paged_supported(self) -> bool:
        """Paged decode covers the plain attention families: uniform
        full-attention layers, no sliding windows, no logit softcap (the
        ring-cache path already handles local layers better)."""
        cfg = self.cfg
        return (cfg.family in ("dense", "moe", "audio", "vlm")
                and cfg.window is None and cfg.attn_softcap is None)

    def init_paged_cache(self, batch: int, seq_len: int,
                         page_size: int = 64) -> Dict[str, jax.Array]:
        """KV cache as a pool of fixed-size pages plus an indices table.

        ``table[b, j]`` is the physical page holding slot b's positions
        ``[j*page, (j+1)*page)``.  The static-batch engine initializes it
        slot-major (slot b owns pages ``[b*nb, (b+1)*nb)``), so dense
        prefill rows reshape straight into a slot's pages; the *read* side
        (the decode kernel) only ever sees the table, so a continuous-
        batching allocator can later hand out pages in any order without
        touching the kernel.
        """
        nb = -(-seq_len // page_size)
        table = jnp.arange(batch * nb, dtype=jnp.int32).reshape(batch, nb)
        return dict(self.init_paged_pool(batch * nb, page_size), table=table)

    def init_paged_pool(self, num_pages: int,
                        page_size: int = 64) -> Dict[str, jax.Array]:
        """Bare physical page pool for a continuous-batching allocator.

        Unlike :meth:`init_paged_cache` there is no baked-in table: the
        block manager (``repro.serve.blocks``) owns the logical->physical
        mapping and hands the engine per-tick tables.  Page 0 is reserved
        as the NULL page by convention — inactive slots and unallocated
        table-row tails point there, so stray writes (idle-slot decode,
        prefill end-padding) can never corrupt a live sequence.
        """
        cfg = self.cfg
        assert self.paged_supported(), (
            f"paged decode unsupported for family={cfg.family!r} "
            f"window={cfg.window} softcap={cfg.attn_softcap}")
        L = cfg.n_layers
        if cfg.is_mla:
            # one latent row [c_kv | k_pe] per position, shared by heads
            # (repro.models.mla): c_kv rows, and the rope keys packed
            # ``pack`` positions to a 128-lane row
            from repro.kernels.paged_attention import rope_pack
            pack = rope_pack(cfg.qk_rope_head_dim)
            if page_size % pack:
                raise ValueError(f"page_size {page_size} must be a multiple "
                                 f"of the rope pack {pack}")
            pool = {
                "latent_pages": jnp.zeros(
                    (L, num_pages, page_size, cfg.kv_lora_rank),
                    jnp.bfloat16),
                "rope_pages": jnp.zeros(
                    (L, num_pages, page_size // pack,
                     pack * cfg.qk_rope_head_dim), jnp.bfloat16)}
        else:
            # (L, P, page, Hkv*hd): the heads merged into the minor dim,
            # the block the decode kernel streams, so every layer reads
            # and writes the pool where it lies
            shape = (L, num_pages, page_size, cfg.n_kv_heads * cfg.d_head)
            pool = {"k_pages": jnp.zeros(shape, jnp.bfloat16),
                    "v_pages": jnp.zeros(shape, jnp.bfloat16)}
        if self.holds_experts:
            # decode steps add the tokens routed to each held expert of
            # each MoE layer here, on the device
            pool["held_tokens"] = jnp.zeros(
                (L - cfg.first_k_dense, cfg.n_held_experts), jnp.int32)
        return pool

    @property
    def holds_experts(self) -> bool:
        """Serve MoE layers with the dropless held layer: a latent-attention
        config, or one that holds a share of the experts."""
        cfg = self.cfg
        return cfg.family == "moe" and (cfg.is_mla or cfg.expert_shard[1] > 0)

    def _paged_stack(self, params, x, pool, attend):
        """The layers of a paged serve step over the page pool in the scan
        carry: the leading dense layers (``cfg.first_k_dense``), then the
        rest.  ``attend(h, attn_params, pool, layer)`` is one layer's
        attention and returns (out, pool).  MoE layers take the dropless
        held expert layer (:func:`repro.models.moe.forward_held`) where
        :attr:`holds_experts`, else the capacity layer.  Returns (x, pool,
        tokens routed to each held expert per MoE layer, or None)."""
        cfg = self.cfg
        k = cfg.first_k_dense

        def mlp(h, lp):
            m = lp["mlp"]
            return layers.glu_mlp(h, m["gate"], m["in"], m["out"],
                                  act=cfg.act, policy=self.policy), None

        def routed(h, lp):
            if self.holds_experts:
                return moe.forward_held(h, lp["moe"], cfg, policy=self.policy)
            f, _ = moe.forward(h, lp["moe"], cfg, self.plan, self.mesh,
                               policy=self.policy)
            return f, None

        def scan(x, pool, stacked, lo, hi, ffn):
            def body(carry, xs):
                x, pool = carry
                lp, i = xs
                h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
                a, pool = attend(h, lp["attn"], pool, i)
                x = x + a
                f, held = ffn(layers.rms_norm(x, lp["ln2"], cfg.norm_eps), lp)
                return (x + f, pool), held

            return jax.lax.scan(body, (x, pool),
                                (stacked, jnp.arange(lo, hi)))

        if k:
            (x, pool), _ = scan(x, pool, params["dense_layers"], 0, k, mlp)
        (x, pool), held = scan(x, pool, params["layers"], k, cfg.n_layers,
                               routed if cfg.family == "moe" else mlp)
        return x, pool, held

    def _paged_pool(self, cache):
        names = (("latent_pages", "rope_pages") if self.cfg.is_mla
                 else ("k_pages", "v_pages"))
        return names, tuple(cache[n] for n in names)

    def prefill_chunk_paged(self, params, cache, tokens, table_row, start):
        """One fixed-size prefill chunk for ONE sequence (B=1 forward).

        ``tokens``: (1, C) end-padded chunk; ``table_row``: (n_pages,)
        logical->physical for the sequence; ``start``: absolute position
        of ``tokens[0, 0]``.  Returns per-position logits (1, C, V) — the
        caller samples at the last REAL position of the final chunk — and
        the cache with updated pages.  Shared by the static paged engine
        and the continuous engine so their prefill numerics are
        bit-identical (see ``attention.prefill_chunk_paged``).
        """
        cfg, plan = self.cfg, self.plan
        x = layers.embed(tokens, params["embed"], scale=cfg.emb_scale)
        x = x.astype(jnp.bfloat16)
        kw = dict(policy=self.policy, q_chunk=self.q_chunk,
                  kv_chunk=self.kv_chunk)

        def attend(h, ap, pool, i):
            if cfg.is_mla:
                return mla.prefill_chunk_paged(h, ap, cfg, pool, i,
                                               table_row, start, **kw)
            a, kp, vp = attention.prefill_chunk_paged(
                h, ap, cfg, plan, *pool, i, table_row, start, **kw)
            return a, (kp, vp)

        names, pool = self._paged_pool(cache)
        x, pool, _ = self._paged_stack(params, x, pool, attend)
        cache = dict(cache, **dict(zip(names, pool)))
        logits = self._head(params, x)
        return logits, cache

    def decode_step_paged(self, params, cache, tokens, pos):
        """One-token serve step against the paged cache.  Same contract as
        :meth:`decode_step` with ``cache`` from :meth:`init_paged_cache`.
        Where the cache holds ``held_tokens`` (a held expert share), the
        step adds the tokens it routed to each held expert of each MoE
        layer to it, on the device."""
        cfg, plan = self.cfg, self.plan
        x = layers.embed(tokens, params["embed"], scale=cfg.emb_scale)
        x = x.astype(jnp.bfloat16)
        table = cache["table"]

        def attend(h, ap, pool, i):
            if cfg.is_mla:
                return mla.decode_paged(h, ap, cfg, pool, i, table, pos,
                                        policy=self.policy)
            a, kp, vp = attention.decode_paged(
                h, ap, cfg, plan, *pool, i, table, pos, policy=self.policy)
            return a, (kp, vp)

        names, pool = self._paged_pool(cache)
        x, pool, held = self._paged_stack(params, x, pool, attend)
        cache = dict(cache, **dict(zip(names, pool)))
        if held is not None:
            cache["held_tokens"] = cache["held_tokens"] + held
        logits = self._head(params, x)
        return logits, cache

    def prefill(self, params, tokens, vision_embeds=None,
                last_only: bool = True):
        """Full-sequence forward returning logits + decode-ready cache.

        ``last_only`` (serving default) computes the LM head only for the
        final position — the full-sequence fp32 logits would be the single
        largest prefill buffer (gemma3: 4.3 GiB/device at 32k).
        """
        cfg, plan = self.cfg, self.plan
        logits, _, caches = self.forward(params, tokens, vision_embeds,
                                         with_cache=True,
                                         last_only=last_only)
        B = tokens.shape[0]
        cache: Dict[str, jax.Array] = {}
        if cfg.family in ("dense", "moe", "audio", "vlm"):
            k, v = caches                      # (L, B, S, Hkv, hd) stacked
            lay = plan.kv_cache(B, self.mesh)
            if self._windowed():
                L = cfg.n_layers
                S = k.shape[2]
                gids = [i for i in range(L) if cfg.is_global_layer(i)]
                lids = [i for i in range(L) if not cfg.is_global_layer(i)]
                W = min(cfg.window, max(S, 1))
                # ring slot j holds the LAST position p == j (mod W):
                # p_j = S-1 - ((S-1-j) mod W); p_j < 0 slots are masked by
                # the decode-side abs-position formula, content irrelevant
                j = jnp.arange(W)
                p_j = jnp.clip(S - 1 - jnp.mod(S - 1 - j, W), 0, S - 1)
                cache["k_g"] = constrain(
                    k[jnp.asarray(gids, jnp.int32)].astype(jnp.bfloat16), lay)
                cache["v_g"] = constrain(
                    v[jnp.asarray(gids, jnp.int32)].astype(jnp.bfloat16), lay)
                cache["k_l"] = jnp.take(
                    k[jnp.asarray(lids, jnp.int32)], p_j, axis=2).astype(jnp.bfloat16)
                cache["v_l"] = jnp.take(
                    v[jnp.asarray(lids, jnp.int32)], p_j, axis=2).astype(jnp.bfloat16)
                return logits, cache
            cache["k"] = constrain(k.astype(jnp.bfloat16), lay)
            cache["v"] = constrain(v.astype(jnp.bfloat16), lay)
        elif cfg.family == "ssm":
            conv, sstate, bc = caches
            cache["conv"] = conv
            cache["ssm"] = sstate
            cache["bc_conv"] = bc
        else:
            (sstates, tail_states), site_caches = caches
            # head states come back (n_sites, every, B, ...) — flatten to
            # (L, B, ...) and append the mamba tail
            def _flat(head, tail):
                head = head.reshape((-1,) + head.shape[2:])
                return (jnp.concatenate([head, tail], 0)
                        if tail is not None else head)
            conv, sstate, bc = (
                _flat(h, t) for h, t in zip(
                    sstates, tail_states if tail_states is not None
                    else (None, None, None)))
            cache["conv"] = conv
            cache["ssm"] = sstate
            cache["bc_conv"] = bc
            lay = plan.kv_cache(B, self.mesh)
            cache["k"] = constrain(site_caches[0], lay)
            cache["v"] = constrain(site_caches[1], lay)
        return logits, cache

    def decode_step(self, params, cache, tokens, pos):
        """One-token serve step.  tokens: (B, 1); pos: scalar int32."""
        cfg, plan = self.cfg, self.plan
        x = layers.embed(tokens, params["embed"], scale=cfg.emb_scale)
        x = x.astype(jnp.bfloat16)
        windows = self._window_array(int(cache["k"].shape[2])
                                     if "k" in cache else 0)

        def mlp_tail(x, lp):
            h = layers.rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                f, _ = moe.forward(h, lp["moe"], cfg, plan, self.mesh,
                                   policy=self.policy)
            else:
                f = layers.glu_mlp(
                    h, lp["mlp"]["gate"], lp["mlp"]["in"],
                    lp["mlp"]["out"], act=cfg.act, policy=self.policy)
            return x + f

        # Caches ride in the scan CARRY with per-layer dynamic updates so
        # XLA keeps them in place (donated buffers); emitting them as scan
        # ys would allocate a full second cache (measured: +2x cache bytes
        # on musicgen decode_32k — see EXPERIMENTS §Dry-run notes).
        if "k_l" in cache:
            # interleaved local/global (gemma3): static groups of
            # `pattern` ring-cached local layers + 1 full-cache global
            pat = cfg.local_global_pattern
            period = pat + 1
            n_groups = cfg.n_layers // period
            n_tail = cfg.n_layers - n_groups * period

            def local_body(x, xs):
                lp, kr, vr = xs
                h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
                a, kr, vr = attention.decode_ring(
                    h, lp["attn"], cfg, plan, kr, vr, pos,
                    policy=self.policy)
                return mlp_tail(x + a, lp), (kr, vr)

            def group_body(x, xs):
                gp, kl_g, vl_g, kg, vg = xs
                lp_loc = jax.tree.map(lambda a: a[:pat], gp)
                lp_glb = jax.tree.map(lambda a: a[pat], gp)
                x, (kl_g, vl_g) = jax.lax.scan(
                    local_body, x, (lp_loc, kl_g, vl_g))
                h = layers.rms_norm(x, lp_glb["ln1"], cfg.norm_eps)
                a, kg, vg = attention.decode(
                    h, lp_glb["attn"], cfg, plan, kg, vg, pos,
                    policy=self.policy)
                x = mlp_tail(x + a, lp_glb)
                return x, (kl_g, vl_g, kg, vg)

            n_head = n_groups * period
            head_p = jax.tree.map(
                lambda a: a[:n_head].reshape((n_groups, period)
                                             + a.shape[1:]),
                params["layers"])
            kl_h = cache["k_l"][:n_groups * pat].reshape(
                (n_groups, pat) + cache["k_l"].shape[1:])
            vl_h = cache["v_l"][:n_groups * pat].reshape(
                (n_groups, pat) + cache["v_l"].shape[1:])
            if n_groups:
                x, (kl_new, vl_new, kg_new, vg_new) = jax.lax.scan(
                    group_body, x, (head_p, kl_h, vl_h, cache["k_g"],
                                    cache["v_g"]))
                kl_new = kl_new.reshape((-1,) + kl_new.shape[2:])
                vl_new = vl_new.reshape((-1,) + vl_new.shape[2:])
            else:
                kl_new = cache["k_l"][:0]
                vl_new = cache["v_l"][:0]
                kg_new, vg_new = cache["k_g"], cache["v_g"]
            if n_tail:                      # trailing local layers
                tail_p = jax.tree.map(lambda a: a[n_head:],
                                      params["layers"])
                x, (kt, vt) = jax.lax.scan(
                    local_body, x,
                    (tail_p, cache["k_l"][n_groups * pat:],
                     cache["v_l"][n_groups * pat:]))
                kl_new = jnp.concatenate([kl_new, kt], 0)
                vl_new = jnp.concatenate([vl_new, vt], 0)
            cache = dict(cache, k_l=kl_new, v_l=vl_new, k_g=kg_new,
                         v_g=vg_new)

        elif cfg.family in ("dense", "moe", "audio", "vlm"):
            def body(carry, xs):
                x, ck, cv = carry
                if windows is not None:
                    lp, i, win = xs
                else:
                    (lp, i), win = xs, None
                kc, vc = ck[i], cv[i]
                h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
                a, kc, vc = attention.decode(
                    h, lp["attn"], cfg, plan, kc, vc, pos,
                    policy=self.policy, window=win)
                x = x + a
                h = layers.rms_norm(x, lp["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    f, _ = moe.forward(h, lp["moe"], cfg, plan, self.mesh,
                                       policy=self.policy)
                else:
                    f = layers.glu_mlp(
                        h, lp["mlp"]["gate"], lp["mlp"]["in"],
                        lp["mlp"]["out"], act=cfg.act, policy=self.policy)
                ck = jax.lax.dynamic_update_index_in_dim(ck, kc, i, 0)
                cv = jax.lax.dynamic_update_index_in_dim(cv, vc, i, 0)
                return (x + f, ck, cv), None

            idx = jnp.arange(cfg.n_layers)
            xs = ((params["layers"], idx, windows)
                  if windows is not None else (params["layers"], idx))
            (x, k_new, v_new), _ = jax.lax.scan(
                body, (x, cache["k"], cache["v"]), xs)
            cache = dict(cache, k=k_new, v=v_new)

        elif cfg.family == "ssm":
            def body(carry, xs):
                x, conv_a, ssm_a, bc_a = carry
                lp, i = xs
                h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
                y, conv, sstate, bc = ssm.decode_step(
                    h, lp["ssm"], cfg, plan, conv_a[i], ssm_a[i], bc_a[i],
                    policy=self.policy)
                conv_a = jax.lax.dynamic_update_index_in_dim(
                    conv_a, conv.astype(conv_a.dtype), i, 0)
                ssm_a = jax.lax.dynamic_update_index_in_dim(
                    ssm_a, sstate.astype(ssm_a.dtype), i, 0)
                bc_a = jax.lax.dynamic_update_index_in_dim(
                    bc_a, bc.astype(bc_a.dtype), i, 0)
                return (x + y, conv_a, ssm_a, bc_a), None

            (x, conv, sstate, bc), _ = jax.lax.scan(
                body, (x, cache["conv"], cache["ssm"], cache["bc_conv"]),
                (params["layers"], jnp.arange(cfg.n_layers)))
            cache = dict(cache, conv=conv, ssm=sstate, bc_conv=bc)

        else:  # hybrid: same static group structure as forward — no cond
            every = cfg.attn_every
            shared = params["shared"]
            n_sites = cfg.n_layers // every
            n_head = n_sites * every
            n_tail = cfg.n_layers - n_head

            def split(a):
                return (jax.tree.map(lambda t: t[:n_head].reshape(
                            (n_sites, every) + t.shape[1:]), a),
                        jax.tree.map(lambda t: t[n_head:], a))

            head_p, tail_p = split(params["layers"])
            conv_h, conv_t = split(cache["conv"])
            ssm_h, ssm_t = split(cache["ssm"])
            bc_h, bc_t = split(cache["bc_conv"])

            def mamba_body(x, xs):
                lp, conv, sstate, bcs = xs
                h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
                y, conv, sstate, bcs = ssm.decode_step(
                    h, lp["ssm"], cfg, plan, conv, sstate, bcs,
                    policy=self.policy)
                return x + y, (conv.astype(cache["conv"].dtype),
                               sstate.astype(cache["ssm"].dtype),
                               bcs.astype(cache["bc_conv"].dtype))

            def group_body(x, xs):
                gp, conv_g, ssm_g, bc_g, kc, vc = xs
                x, states = jax.lax.scan(mamba_body, x,
                                         (gp, conv_g, ssm_g, bc_g))
                h = layers.rms_norm(x, shared["ln1"], cfg.norm_eps)
                a, kc, vc = attention.decode(
                    h, shared["attn"], cfg, plan, kc, vc, pos,
                    policy=self.policy)
                x = x + a
                h = layers.rms_norm(x, shared["ln2"], cfg.norm_eps)
                f = layers.glu_mlp(
                    h, shared["mlp"]["gate"], shared["mlp"]["in"],
                    shared["mlp"]["out"], act=cfg.act, policy=self.policy)
                return x + f, (states, kc, vc)

            x, (head_states, k_new, v_new) = jax.lax.scan(
                group_body, x,
                (head_p, conv_h, ssm_h, bc_h, cache["k"], cache["v"]))
            if n_tail:
                x, tail_states = jax.lax.scan(
                    mamba_body, x, (tail_p, conv_t, ssm_t, bc_t))
            conv, sstate, bc = (
                (jnp.concatenate(
                    [h.reshape((-1,) + h.shape[2:]), t], 0) if n_tail
                 else h.reshape((-1,) + h.shape[2:]))
                for h, t in zip(head_states,
                                tail_states if n_tail else (None,) * 3))
            cache = dict(cache, k=k_new, v=v_new, conv=conv, ssm=sstate,
                         bc_conv=bc)

        logits = self._head(params, x)
        return logits, cache


def _nb(mesh, plan) -> int:
    return math.prod(mesh.shape[a] for a in plan.batch_axes)
