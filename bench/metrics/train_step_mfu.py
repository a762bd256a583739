"""Model FLOP utilisation of training: the forward and backward FLOPs
per token (no recomputation, ``bench/flops.py``) times the window's
tokens per second, over the chips' peak (``bench/peaks.py``)."""

from bench import flops


def read(run):
    r = run.record
    if "tokens" not in r or not r.get("window_s"):
        return None
    per_token = flops.train_flops_per_token(run.shape, run.mix["seq"])
    rate = r["tokens"] / r["window_s"]
    return 100.0 * per_token * rate / (run.chips * run.peak.flops_per_s)
