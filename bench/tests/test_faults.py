"""The harness with the timed path broken underneath: each fault a cell
can have makes ``correct`` come out false, where the sound program
passes.  Runs on the CPU at a tiny size; the look for a chip is skipped.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny

TRAIN = ("tiny-qwen2", "tiny-train")
CHAT = ("tiny-qwen2", "tiny-chat")
LONG = ("tiny-qwen3", "tiny-longdecode")


def run(tmp_path, cell, seconds=1.5):
    root = tiny.make_root(tmp_path, [cell])
    return tiny.run(root, f"{cell[0]}.{cell[1]}", seconds=seconds)


def frozen_apply(cfg, opt_state, grads, param_specs, mesh,
                 decay_mask=None):
    """A step that returns its state unchanged."""
    params = jax.tree.map(lambda m, s: m.astype(s.dtype),
                          opt_state["master"], param_specs)
    zero = jnp.zeros((), jnp.float32)
    return params, opt_state, {"grad_norm": zero, "lr": zero}


def half_batch_loss(orig):
    def loss_fn(self, params, batch):
        half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
        return orig(self, params, half)
    return loss_fn


def altered_sample(orig):
    def _sample(self, logits):
        return (orig(self, logits) + 1) % logits.shape[-1]
    return _sample


def test_sound_train_passes(tmp_path):
    out = run(tmp_path, TRAIN)
    assert out["correct"] is True, out["checks"]


def test_unchanged_state_fails(tmp_path, monkeypatch):
    from repro.train import optimizer
    monkeypatch.setattr(optimizer, "apply", frozen_apply)
    out = run(tmp_path, TRAIN)
    assert out["correct"] is False
    assert out["checks"]["change"]["value"] > 0.5


def test_half_batch_fails(tmp_path, monkeypatch):
    from repro.models.transformer import Model
    monkeypatch.setattr(Model, "loss_fn", half_batch_loss(Model.loss_fn))
    out = run(tmp_path, TRAIN)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", [CHAT, LONG], ids=["chat", "longdecode"])
def test_sound_serve_passes(tmp_path, cell):
    out = run(tmp_path, cell)
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("cell", [CHAT, LONG], ids=["chat", "longdecode"])
def test_altered_token_fails(tmp_path, monkeypatch, cell):
    from repro.serve.engine import ContinuousEngine
    monkeypatch.setattr(ContinuousEngine, "_sample",
                        altered_sample(ContinuousEngine._sample))
    out = run(tmp_path, cell)
    assert out["correct"] is False
    assert out["checks"]["served_gap"]["value"] > tiny.LIMITS["served_gap"]


CHILD = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{repo!r}, {src!r}]
    from bench.tests import tiny
    if {broken!r}:
        from repro.comms import plan
        plan.sync_tree = lambda grads, *a, **k: grads
    root = tiny.make_root({tmp!r}, [("tiny-qwen2", "tiny-train", 4)])
    out = tiny.run(root, "tiny-qwen2.tiny-train", seconds=1.5)
    print(json.dumps(out["checks"]))
    print(json.dumps(out["correct"]))
""")


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "broken"])
def test_exchange_left_out_fails(tmp_path, broken):
    """Four CPU devices, data-parallel: without the gradient exchange
    each chip steps on its own rows' gradient."""
    src = CHILD.format(repo=tiny.REPO, src=os.path.join(tiny.REPO, "src"),
                       tmp=str(tmp_path), broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", src], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    *_, checks, correct = p.stdout.strip().splitlines()
    assert json.loads(correct) is (not broken), checks
