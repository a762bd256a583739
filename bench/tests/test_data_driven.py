"""A cell, a configuration, a traffic mix and a per-layer metric are
added by new files and new BENCHMARK.json entries alone: the copied
harness files are left as they are, and the new cell runs with the new
metric in its traced result."""

import filecmp
import json
import os
import subprocess
import sys

from bench.tests import tiny

NEW_METRIC = '''"""Test metric: the window's length in percent of a minute."""


def read(run):
    return 100.0 * run.record["window_s"] / 60.0
'''

SILENT_METRIC = '''def read(run):
    return None
'''


def test_new_cell_config_mix_and_metric_from_files(tmp_path):
    conf = dict(tiny.TINY_QWEN2, name="tiny-other", hidden_size=128,
                program=dict(tiny.TINY_QWEN2["program"], name="tiny-other",
                             d_model=128, head_dim=32))
    mix = dict(tiny.MIXES["tiny-chat"], rate_per_s=10.0)
    root = tiny.make_root(
        tmp_path, [("tiny-other", "tiny-newmix")], extra_configs=[conf],
        extra_mixes={"tiny-newmix": mix},
        extra_metrics={"window_share": NEW_METRIC,
                       "nothing_to_read": SILENT_METRIC})
    # every harness file the copy shares with the repo is unchanged
    cmp = filecmp.dircmp(os.path.join(tiny.REPO, "bench"),
                         os.path.join(root, "bench"))
    assert not cmp.diff_files
    for sub in ("traffic", "metrics", "configs", "mixes"):
        assert not cmp.subdirs[sub].diff_files
    out = tiny.run(root, "tiny-other.tiny-newmix", seconds=1.5, trace=True)
    assert "window_share" in out["metrics"]
    assert "nothing_to_read" not in out["metrics"]
    assert out["checks"]["served_gap"]["limit"] == tiny.LIMITS["served_gap"]
    assert out["correct"] is True
    assert list(out)[-1] == "checks"


def test_no_chip_no_result(tmp_path):
    """On the CPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.train-4k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(tiny.REPO, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            tiny.REPO, "bench", "mixes", w["traffic"] + ".json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(
            tiny.REPO, "bench", "metrics", m["name"] + ".py"))
