"""Plain float32 AdamW, as the benchmark's train mixes state it.

One step: clip the gradient by its global norm, update both moments,
correct their bias, and step the float32 master weights by
``lr * (m_hat / (sqrt(v_hat) + eps) + wd * master)``, with the decay
only on leaves of two or more dimensions as stored (the per-layer norm
scales and biases are stacked over layers, so they count as such).
The state is updated in place (its buffers are donated), so the
reference fits beside the float32 weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def init(master):
    return {"step": 0, "mu": jax.tree.map(jnp.zeros_like, master),
            "nu": jax.tree.map(jnp.zeros_like, master)}


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _update(master, mu, nu, g, step, opt):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-12))
    b1, b2 = opt["b1"], opt["b2"]
    b1c, b2c = 1.0 - b1 ** step, 1.0 - b2 ** step

    def one(p, m, v, gi):
        gi = gi * scale
        m = b1 * m + (1.0 - b1) * gi
        v = b2 * v + (1.0 - b2) * gi * gi
        d = (m / b1c) / (jnp.sqrt(v / b2c) + opt["eps"])
        decay = opt["weight_decay"] if p.ndim >= 2 else 0.0
        return p - opt["lr"] * (d + decay * p), m, v, \
            jnp.sqrt(jnp.sum(jnp.square(gi)))

    out = jax.tree.map(one, master, mu, nu, g)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def step(opt: dict, master, state, grads):
    """One AdamW step on donated ``master`` and ``state``; returns (master,
    state, per-leaf norms of the clipped gradient the update used)."""
    n = state["step"] + 1
    opt = {k: float(opt[k]) for k in ("lr", "b1", "b2", "eps",
                                      "weight_decay", "grad_clip")}
    master, mu, nu, gnorm = _update(master, state["mu"], state["nu"], grads,
                                    float(n), opt)
    return master, {"step": n, "mu": mu, "nu": nu}, gnorm
