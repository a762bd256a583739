"""The comparisons that decide ``correct``.

Training: each checked step's loss, the first gradient as the optimizer
got it, and the change of the weights over the checked steps, per leaf
of the weight tree.  A leaf's gap is the difference between the
program's norm and the reference's, over the larger of the reference's
norm of that leaf and the median leaf's norm (some gradients are all but
zero).  Leaves whose reference gradient is under a thousandth of the
median leaf's move under Adam by round-off alone and are left out of the
change.  A gap of norms grows only with the square of an error that is
noise, so it hardly tells a float8 computation from a bfloat16 one;
``grad_diff`` takes the norm of the difference of the first gradients
instead, over the same denominator, which grows with the error itself.

Serving: the widest gap by which a served token's reference logit lies
below the reference's best logit at that position.
"""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: a leaf moves by round-off alone below this share of the median leaf's
#: reference gradient norm
ZERO_GRAD = 1e-3


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def norms(tree, scale: float = 1.0) -> Dict[str, float]:
    vals = jax.device_get(_norms(tree))
    return {p: float(v) * scale for p, v in zip(leaf_paths(tree), vals)}


def diff_norms(a, b) -> Dict[str, float]:
    vals = jax.device_get(_diff_norms(a, b))
    return {p: float(v) for p, v in zip(leaf_paths(a), vals)}


def small_leaves(ref_grad: Dict[str, float]) -> set:
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v < ZERO_GRAD * med}


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               skip: Iterable[str] = ()) -> Tuple[float, str]:
    """(largest gap over the leaves, its leaf)."""
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))}")
    skip = set(skip)
    med = statistics.median(v for k, v in ref.items() if k not in skip)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if k not in skip}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def host_leaves(tree, scale: float = 1.0) -> Dict[str, np.ndarray]:
    """{path: float32 host array} of a tree, times ``scale``, leaf by leaf
    (so that no second copy of the tree is made on the device)."""
    out = {}
    for path, x in zip(leaf_paths(tree), jax.tree.leaves(tree)):
        out[path] = np.asarray(jax.device_get(x), np.float32) * np.float32(
            scale)
    return out


def worst_diff(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
               ref_norm: Dict[str, float]) -> Tuple[float, str]:
    """(largest norm of a leaf's difference over the larger of the
    reference's norm of the leaf and the median leaf's, its leaf)."""
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))}")
    med = statistics.median(ref_norm.values())
    gaps = {k: float(np.linalg.norm((prog[k] - ref[k]).ravel()))
            / max(ref_norm[k], med, 1e-30) for k in ref}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def loss_gap(prog, ref) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def train_numbers(prog: dict, ref: dict) -> Dict[str, Tuple[float, str]]:
    """{number: (value, where)} from the program's and the reference's
    readings: ``losses`` (per checked step), ``grad`` and ``change``
    (per-leaf norms) and ``grad_full`` (the first gradient, on the host)."""
    skip = small_leaves(ref["grad"])
    g, g_leaf = worst_leaf(prog["grad"], ref["grad"])
    c, c_leaf = worst_leaf(prog["change"], ref["change"], skip)
    d, d_leaf = worst_diff(prog["grad_full"], ref["grad_full"], ref["grad"])
    return {"loss": (loss_gap(prog["losses"], ref["losses"]), "steps"),
            "grad": (g, g_leaf),
            "change": (c, c_leaf + f" ({len(skip)} leaves left out)"),
            "grad_diff": (d, d_leaf)}


def served_gap(ref_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit of
    the token served."""
    best = ref_logits.max(-1)
    return best - np.take_along_axis(ref_logits, served[:, None], -1)[:, 0]
