"""The latent-attention serve ticks' share of the chip's peak in the traced
window: for each decode tick the least time its work could take
(``bench/flops_mla.py``: the held weights once and the live latent rows
against peak bandwidth, or its FLOPs against peak FLOP/s, whichever
binds), summed over the ticks, over the ticks' measured time.  The cell
prefills in set-up, so its window holds decode ticks only."""

from bench import flops, flops_mla
from bench.reference import deepseek_v2


def read(run):
    ticks, conf = run.record.get("traced_ticks"), run.record.get("mla")
    if not ticks or not conf:
        return None
    s = deepseek_v2.shape_of(conf)
    bound = sum(flops.roofline_seconds(
        *flops_mla.decode_tick(s, t["decode"]), run.peak)
        for t in ticks if t["decode"])
    spent = sum(t["s"] for t in ticks)
    if bound <= 0 or spent <= 0:
        return None
    return 100.0 * bound / spent
