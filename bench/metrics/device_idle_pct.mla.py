"""Share of the traced window in which no op ran on the device, averaged
over the chips (``bench/trace_reduce.py``), in the latent-attention decode
cell: its idle gaps are the serve loop's host time between ticks."""


def read(run):
    t = run.trace
    if t is None or not t.devices or t.window_s <= 0 \
            or "mla" not in run.record:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
