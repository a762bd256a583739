"""Nested manual shard_map: a model shard_map inside the explicit train
step's outer, fully-manual shard_map runs its body inline."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import manual
from repro.core.layout import Layout, constrain
from repro.launch.mesh import make_mesh
from repro.models import layers


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _embed_inputs():
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 50, (2, 8)), jnp.int32)
    table = jnp.asarray(rng.normal(size=(50, 16)), jnp.float32)
    return tokens, table


def _outer(mesh, body):
    """The comms train step's wrapping: fully manual over every axis."""
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(("data",)), P()),
        out_specs=P(("data",)), check_vma=False))


def test_manual_axes_empty_outside_and_full_inside(mesh):
    assert manual.manual_axes() == frozenset()
    seen = []

    def body(x):
        seen.append(manual.manual_axes())
        return x

    jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P()))(
        jnp.ones(4))
    assert seen == [frozenset({"data", "model"})]


def test_nested_model_shard_map_runs_inline_and_matches(mesh):
    tokens, table = _embed_inputs()

    def embed(tok, tab):
        return layers.embed_shard_map(tok, tab, mesh, batch_axes=("data",),
                                      tp_axis="model", scale=True)

    with jax.set_mesh(mesh):
        plain = jax.jit(embed)(tokens, table)
        nested = _outer(mesh, embed)(tokens, table)
    np.testing.assert_array_equal(np.asarray(nested), np.asarray(plain))
    want = np.asarray(table)[np.asarray(tokens)] * 16 ** 0.5
    np.testing.assert_allclose(np.asarray(plain), want, rtol=1e-6)


def test_bare_jax_shard_map_refuses_the_nesting(mesh):
    """Why the helper exists: JAX itself rejects the nested call."""
    tokens, table = _embed_inputs()

    def embed(tok, tab):
        return jax.shard_map(
            lambda a, b: jnp.take(b, a, axis=0), mesh=mesh,
            in_specs=(P(("data",), None), P(None, "model")),
            out_specs=P(("data",), None, "model"), check_vma=False)(tok, tab)

    with jax.set_mesh(mesh), pytest.raises(ValueError, match="mesh"):
        _outer(mesh, embed)(tokens, table)


def test_constrain_drops_manual_axes(mesh):
    lay = Layout((("data",), None))

    def body(x):
        return constrain(x, lay) * 2

    with jax.set_mesh(mesh):
        out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                    out_specs=P()))(jnp.ones((2, 3)))
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((2, 3)))
