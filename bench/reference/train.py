"""The reference's training steps: the plain float32 model and AdamW on
the same seeded weights and batches as the program's first steps.

It goes one sequence at a time (``dense_gqa.loss_and_grad``) and sums
the gradients in float32, so it fits where the batch would not.  Its
readings are those :func:`bench.compare.train_numbers` compares: the
loss of each step, the per-leaf norm of the first clipped gradient (and
the gradient itself, on the host) and the per-leaf norm of the change of
the weights over the steps.

``fault`` plants a fault in the reference put in the program's place,
for reading where a fault lands: ``half_batch`` takes the loss and the
gradient over the first half of the rows (of a batch of one row, over
the first half of its tokens); ``no_exchange`` keeps the loss of all
rows but the gradient of the first chip's rows only.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

from bench import compare, weights
from bench.reference import adamw, dense_gqa


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(g, k):
    return jax.tree.map(lambda x: x * k, g)


@jax.jit
def _clip_scale(g, clip):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    return jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))


@jax.jit
def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def run(shape, seed: int, batches: List[dict], opt: dict, *,
        cast: str = "f32", fault: Optional[str] = None,
        chips: int = 1) -> dict:
    cast_fn = dense_gqa.CASTS[cast]
    master = _f32(weights.make(shape, seed))
    state = adamw.init(master)
    losses, grad, grad_full = [], None, None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            rows, seq = b["tokens"].shape
            half = fault == "half_batch"
            keep = rows // 2 if half and rows > 1 else rows
            cut = seq // 2 if half and rows == 1 else seq
            loss_rows = range(keep)
            grad_rows = range(rows // chips if fault == "no_exchange"
                              else keep)
            g_sum, l_sum = None, 0.0
            for r in sorted(set(loss_rows) | set(grad_rows)):
                loss, g = dense_gqa.loss_and_grad(
                    master, jnp.asarray(b["tokens"][r][:cut]),
                    jnp.asarray(b["labels"][r][:cut]), shape, cast_fn)
                if r in loss_rows:
                    l_sum += float(loss)
                if r in grad_rows:
                    g_sum = g if g_sum is None else _add(g_sum, g)
                del g
            g_sum = _scale(g_sum, 1.0 / len(grad_rows))
            losses.append(l_sum / len(loss_rows))
            if i == 0:
                grad_full = compare.host_leaves(g_sum, float(_clip_scale(
                    g_sum, float(opt["grad_clip"]))))
            master, state, gnorm = adamw.step(opt, master, state, g_sum)
            del g_sum
            if i == 0:
                grad = dict(zip(compare.leaf_paths(gnorm),
                                map(float, jax.device_get(
                                    jax.tree.leaves(gnorm)))))
    del state
    change = compare.diff_norms(master, weights.make(shape, seed))
    return {"losses": losses, "grad": grad, "grad_full": grad_full,
            "change": change}
