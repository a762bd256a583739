"""The serve ticks' share of the chip's peak in the traced window: for
each tick the least time its work could take (``bench/flops.py``: per
decode step the weights once and the live KV against peak bandwidth, or
its FLOPs against peak FLOP/s, whichever binds; per prefill chunk the
same), summed over the ticks, over the ticks' measured time."""

from bench import flops


def read(run):
    ticks = run.record.get("traced_ticks")
    if not ticks:
        return None
    bound = 0.0
    for t in ticks:
        if t["decode"]:
            bound += flops.roofline_seconds(
                *flops.decode_tick(run.shape, t["decode"]), run.peak)
        for start, n, last in t["prefill"]:
            bound += flops.roofline_seconds(
                *flops.prefill_chunk(run.shape, start, n, last), run.peak)
    spent = sum(t["s"] for t in ticks)
    if bound <= 0 or spent <= 0:
        return None
    return 100.0 * bound / spent
