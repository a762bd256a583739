"""A benchmark configuration file and what the benchmark reads from it.

``bench/configs/<name>.json`` holds one configuration as it is run: the
published ``config.json`` keys of the model (with any key changed from
the source listed in ``reduced``), the registry arch the program starts
from, and the program's own field overrides under ``program``.  The
benchmark's yardsticks (FLOPs, bytes, the reference, the weights) read
only the published keys, through :class:`Shape`; the program is handed a
``ModelConfig`` built from the registry entry with ``dataclasses.replace``.
"""

from __future__ import annotations

import dataclasses
import json
import os


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the yardsticks need, read from the published keys."""

    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    qk_norm: bool
    tied: bool
    rope_theta: float
    norm_eps: float

    @property
    def layer_params(self) -> int:
        """Matmul parameters of one layer: q, k, v, o and the gated MLP."""
        D, hd = self.d_model, self.head_dim
        return D * hd * (2 * self.heads + 2 * self.kv_heads) \
            + 3 * D * self.d_ff

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one position over all layers."""
        return self.layers * 2 * self.kv_heads * self.head_dim * 2


def load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def shape_of(conf: dict) -> Shape:
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    return Shape(
        name=conf["name"],
        layers=conf["num_hidden_layers"],
        d_model=D,
        heads=H,
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or D // H,
        d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        qkv_bias=bool(conf.get("attention_bias", conf.get("qkv_bias"))),
        qk_norm=bool(conf.get("qk_norm", False)),
        tied=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
    )


def program_config(conf: dict):
    """The program's ``ModelConfig`` for this file: the registry arch
    with the file's ``program`` overrides, checked against the published
    keys so that the two descriptions cannot drift apart."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["program"])
    s = shape_of(conf)
    got = dict(layers=cfg.n_layers, d_model=cfg.d_model, heads=cfg.n_heads,
               kv_heads=cfg.n_kv_heads, head_dim=cfg.d_head, d_ff=cfg.d_ff,
               vocab=cfg.vocab_size, qkv_bias=cfg.qkv_bias,
               qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
               norm_eps=cfg.norm_eps)
    bad = {k: (v, getattr(s, k)) for k, v in got.items()
           if v != getattr(s, k)}
    if bad:
        raise ValueError(f"{conf['name']}: program config differs from the "
                         f"file's published keys (program, file): {bad}")
    if s.tied:
        raise ValueError(f"{conf['name']}: the program unties embeddings; "
                         "the file must say tie_word_embeddings false")
    return cfg


def config_path(root: str, bench: dict, name: str) -> str:
    for c in bench["configs"]:
        if c["name"] == name:
            return os.path.join(root, c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")
