"""Seeded random weights of the MLA configuration, made on the device in
one jitted call, laid out as the program stores them: the leading dense
layers stacked under ``dense_layers``, the MoE layers under ``layers``,
attention as ``wq`` (D, H, nope+rope), ``wkv_a`` (D, kv_rank+rope),
``kv_norm``, ``wk_b`` (kv_rank, H, nope), ``wv_b`` (kv_rank, H, v),
``wo`` (H, v, D); each MoE layer a float32 router over every routed
expert and the held experts' banks.  Scales as in ``bench/weights.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.deepseek_v2 import MLAShape
from bench.weights import _draw, _is_leaf, check_against, seed_key

__all__ = ["layout", "make", "check_against"]


def _attn(s: MLAShape, n: int) -> dict:
    D, H, r = s.d_model, s.heads, s.kv_rank
    return {"wq": ((n, D, H, s.nope + s.rope), "w"),
            "wkv_a": ((n, D, r + s.rope), "w"),
            "kv_norm": ((n, r), "norm"),
            "wk_b": ((n, r, H, s.nope), "w"),
            "wv_b": ((n, r, H, s.v_dim), "w"),
            "wo": ((n, H, s.v_dim, D), "w_out")}


def layout(s: MLAShape) -> dict:
    """{path: (shape, kind)}; kind ``router`` is drawn as ``w`` in
    float32."""
    D, F, Fe, V = s.d_model, s.d_ff, s.d_expert, s.vocab
    L, k, E, Eh, Fs = s.moe_layers, s.first_dense, s.experts, s.held[1], \
        s.shared * s.d_expert
    tree = {
        "embed": ((V, D), "w"),
        "unembed": ((D, V), "w"),
        "final_norm": ((D,), "norm"),
        "layers": {
            "ln1": ((L, D), "norm"), "ln2": ((L, D), "norm"),
            "attn": _attn(s, L),
            "moe": {"router": ((L, D, E), "router"),
                    "w_gate": ((L, Eh, D, Fe), "w"),
                    "w_in": ((L, Eh, D, Fe), "w"),
                    "w_out": ((L, Eh, Fe, D), "w_out"),
                    "shared_gate": ((L, D, Fs), "w"),
                    "shared_in": ((L, D, Fs), "w"),
                    "shared_out": ((L, Fs, D), "w_out")},
        },
    }
    if k:
        tree["dense_layers"] = {
            "ln1": ((k, D), "norm"), "ln2": ((k, D), "norm"),
            "attn": _attn(s, k),
            "mlp": {"gate": ((k, D, F), "w"), "in": ((k, D, F), "w"),
                    "out": ((k, F, D), "w_out")}}
    return tree


def _body(key, s: MLAShape, dtype):
    leaves, treedef = jax.tree.flatten(layout(s), is_leaf=_is_leaf)
    out = []
    for i, (shp, kind) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if kind == "router":
            out.append(_draw(k, shp, "w", s.layers, jnp.float32))
        else:
            out.append(_draw(k, shp, kind, s.layers, dtype))
    return jax.tree.unflatten(treedef, out)


_make = jax.jit(_body, static_argnames=("s", "dtype"))


def make(s: MLAShape, seed: int, dtype=jnp.bfloat16, shardings=None):
    """The weights of ``s`` for ``seed``, in one jitted call on the device
    (placed by ``shardings``, a matching tree, when given)."""
    if shardings is None:
        return _make(seed_key(seed), s, dtype)
    fn = jax.jit(functools.partial(_body, s=s, dtype=dtype),
                 out_shardings=shardings)
    return fn(seed_key(seed))
