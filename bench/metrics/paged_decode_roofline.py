"""The paged decode attention kernel (op ``dmath_paged_decode``) against
its roofline: per call, the larger of its FLOPs over peak FLOP/s and its
bytes over peak bandwidth (``bench/flops.py``: the live K and V of each
slot, the query and the output), summed over the calls in the traced
window, over the kernel's summed device time in the trace."""

from bench import flops

OP = "dmath_paged_decode"


def read(run):
    t, ticks = run.trace, run.record.get("traced_ticks")
    if t is None or not ticks:
        return None
    n_dev, kernel_s = t.op_seconds(OP)
    calls = sum(d.op_count.get(OP, 0) for d in t.devices) / max(1, n_dev)
    decode = [tk["slots"] for tk in ticks if tk["decode"]]
    if not kernel_s or not calls or not decode:
        return None
    per_call = sum(flops.roofline_seconds(
        *flops.paged_decode_call(run.shape, lens), run.peak)
        for lens in decode) / len(decode)
    return 100.0 * per_call * calls / kernel_s
