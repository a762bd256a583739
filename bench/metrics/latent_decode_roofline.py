"""The latent paged decode kernel (op ``dmath_paged_decode_latent``)
against its roofline: per call, the larger of its FLOPs over peak FLOP/s
and its bytes over peak bandwidth (``bench/flops_mla.py``: the live
latent rows of each slot, the query and the output), summed over the
calls in the traced window, over the kernel's summed device time."""

from bench import flops, flops_mla
from bench.reference import deepseek_v2

OP = "dmath_paged_decode_latent"


def read(run):
    t, ticks = run.trace, run.record.get("traced_ticks")
    conf = run.record.get("mla")
    if t is None or not ticks or not conf:
        return None
    s = deepseek_v2.shape_of(conf)
    n_dev, kernel_s = t.op_seconds(OP)
    calls = sum(d.op_count.get(OP, 0) for d in t.devices) / max(1, n_dev)
    decode = [tk["slots"] for tk in ticks if tk["decode"]]
    if not kernel_s or not calls or not decode:
        return None
    per_call = sum(flops.roofline_seconds(
        *flops_mla.latent_decode_call(s, lens), run.peak)
        for lens in decode) / len(decode)
    return 100.0 * per_call * calls / kernel_s
