"""A tiny benchmark tree for the CPU tests: the benchmark's own files
copied into a temporary root, plus tiny configurations, mixes and limits
added as new files, the way a later change adds a cell."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_QWEN2 = {
    "name": "tiny-qwen2", "source": "test", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "qkv_bias": True, "reduced": [],
    "arch": "qwen2-0.5b",
    "program": {"name": "tiny-qwen2", "n_layers": 2, "d_model": 64,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                "vocab_size": 512},
}
TINY_QWEN3 = dict(
    TINY_QWEN2, name="tiny-qwen3", qkv_bias=False, qk_norm=True,
    head_dim=16, arch="qwen3-14b",
    program=dict(TINY_QWEN2["program"], name="tiny-qwen3"))

MIXES = {
    "tiny-train": {
        "kind": "train_fixed_batch", "seq": 64, "batch_per_chip": 4,
        "microbatches": None, "comms": "auto",
        "adamw": {"lr": 0.001, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                  "weight_decay": 0.1, "grad_clip": 1.0},
        "data": {"n_patterns": 256, "pattern_len": 8}, "checked_steps": 3,
        "check_memory": True, "ahead_s": 0.5, "trace_s": 1.0},
    "tiny-chat": {
        "kind": "serve_open_loop", "slots": 4, "max_seq": 256,
        "prefill_chunk": 32, "rate_per_s": 20.0, "block": 4,
        "prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 120},
        "output": {"median": 8, "sigma": 0.6, "min": 2, "max": 24},
        "ramp_s": 0.5, "drain_s": 60.0, "sample_tokens": 40,
        "sample_max": 4, "trace_s": 1.0},
    "tiny-longdecode": {
        "kind": "serve_closed_decode", "slots": 4, "max_seq": 2048,
        "prefill_chunk": 32, "prompt_min": 64, "prompt_max": 160,
        "sample_requests": 2, "trace_s": 1.0},
}

#: limits for the tiny cells, about ten times what sound runs read at
#: this size (loss 1.3e-4, grad 1.6e-3, change 1.1e-2, grad_diff 5.9e-3,
#: served gap under 1e-3)
LIMITS = {"loss": 1e-3, "grad": 1e-2, "change": 0.1, "grad_diff": 0.05,
          "served_gap": 0.05, "unserved": 0.0}


def make_root(tmp, cells, extra_configs=(), extra_mixes=None,
              extra_metrics=None):
    """A benchmark root under ``tmp`` with the repo's ``bench`` files and
    the tiny cells ``[(config, mix[, chips])]``."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    os.makedirs(os.path.join(root, "bench", "limits"), exist_ok=True)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    confs = {c["name"]: c for c in (TINY_QWEN2, TINY_QWEN3,
                                    *extra_configs)}
    mixes = dict(MIXES, **(extra_mixes or {}))
    bench["configs"], bench["workloads"] = [], []
    for cell in cells:
        conf_name, mix = cell[:2]
        chips = cell[2] if len(cell) > 2 else 1
        conf = confs[conf_name]
        path = f"bench/configs/{conf_name}.json"
        if not any(c["name"] == conf_name for c in bench["configs"]):
            with open(os.path.join(root, path), "w") as fh:
                json.dump(conf, fh)
            bench["configs"].append({"name": conf_name, "source": "test",
                                     "file": path, "reduced": [],
                                     "why": "test"})
        with open(os.path.join(root, "bench", "mixes", mix + ".json"),
                  "w") as fh:
            json.dump(mixes[mix], fh)
        name = f"{conf_name}.{mix}"
        bench["workloads"].append({"name": name, "config": conf_name,
                                   "traffic": mix, "chips": chips,
                                   "why": "test"})
        with open(os.path.join(root, "bench", "limits", name + ".json"),
                  "w") as fh:
            json.dump({"limits": LIMITS}, fh)
    names = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m["workloads"] = names
    for name, body in (extra_metrics or {}).items():
        with open(os.path.join(root, "bench", "metrics", name + ".py"),
                  "w") as fh:
            fh.write(body)
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "host_clock", "layer": "test", "moves": "setup_s",
            "workloads": names})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def run(root, workload, seed=7, seconds=2.0, trace=False):
    from bench import harness
    from bench.peaks import Peak
    stand_in = Peak("cpu", 1e12, 1e11, 16 * 1024**3, "test stand-in")
    return harness.run_cell(root, workload, seed, seconds, trace,
                            require_chip=False, peak=stand_in)
