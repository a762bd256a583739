"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference can make
the same ones again from the seed and takes nothing the program made.
The tree is laid out as the program stores a dense decoder (layers
stacked on a leading axis, attention weights split by head); the harness
checks it against the program's own parameter shapes before handing it
over.  Scales follow the usual initialisation (0.02, output projections
scaled by depth); biases and norm scales are drawn away from 0 and 1 so
that the reference comparison covers them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.model_config import Shape


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may pass 2**31)."""
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def layout(s: Shape) -> dict:
    """{path: (shape, kind)}: the weight tree as ``(shape, init kind)``."""
    L, D, H, K, hd, F, V = (s.layers, s.d_model, s.heads, s.kv_heads,
                            s.head_dim, s.d_ff, s.vocab)
    attn = {"wq": ((L, D, H, hd), "w"), "wk": ((L, D, K, hd), "w"),
            "wv": ((L, D, K, hd), "w"), "wo": ((L, H, hd, D), "w_out")}
    if s.qkv_bias:
        attn.update(bq=((L, H, hd), "bias"), bk=((L, K, hd), "bias"),
                    bv=((L, K, hd), "bias"))
    if s.qk_norm:
        attn.update(q_norm=((L, hd), "norm"), k_norm=((L, hd), "norm"))
    tree = {
        "embed": ((V, D), "w"),
        "final_norm": ((D,), "norm"),
        "layers": {
            "ln1": ((L, D), "norm"), "ln2": ((L, D), "norm"),
            "attn": attn,
            "mlp": {"gate": ((L, D, F), "w"), "in": ((L, D, F), "w"),
                    "out": ((L, F, D), "w_out")},
        },
    }
    if not s.tied:
        tree["unembed"] = ((D, V), "w")
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _draw(key, shape, kind, layers, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        x = 0.02 * z
    elif kind == "w_out":
        x = (0.02 / (2 * layers) ** 0.5) * z
    elif kind == "bias":
        x = 0.05 * z
    else:                                   # norm scale
        x = 1.0 + 0.05 * z
    return x.astype(dtype)


def _body(key, s: Shape, dtype):
    leaves, treedef = jax.tree.flatten(layout(s), is_leaf=_is_leaf)
    out = [_draw(jax.random.fold_in(key, i), shp, kind, s.layers, dtype)
           for i, (shp, kind) in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)


_make = jax.jit(_body, static_argnames=("s", "dtype"))


def make(s: Shape, seed: int, dtype=jnp.bfloat16, shardings=None):
    """The weights of ``s`` for ``seed``, in one jitted call on the
    device (placed by ``shardings``, a matching tree, when given)."""
    if shardings is None:
        return _make(seed_key(seed), s, dtype)
    fn = jax.jit(functools.partial(_body, s=s, dtype=dtype),
                 out_shardings=shardings)
    return fn(seed_key(seed))


def check_against(ours, program_sds) -> None:
    """Raise unless ``ours`` has the program's parameter paths and
    shapes (``Model.param_sds()``)."""
    def shapes(tree):
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        return {jax.tree_util.keystr(p): tuple(x.shape) for p, x in flat}

    a, b = shapes(ours), shapes(program_sds)
    if a != b:
        raise ValueError(f"weight tree differs from the program's: "
                         f"{sorted(set(a.items()) ^ set(b.items()))[:6]}")
