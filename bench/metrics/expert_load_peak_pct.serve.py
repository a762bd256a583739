"""Load of the busiest held expert over the held experts' mean, in
percent: the tokens routed to each (MoE layer, held expert) over the
window's decode ticks, read from the counter the program's decode steps
keep on the device (``ContinuousEngine.held_tokens``; 100 is even load)."""


def read(run):
    held = run.record.get("held_tokens")
    if not held:
        return None
    counts = [n for layer in held for n in layer]
    mean = sum(counts) / len(counts)
    return None if mean <= 0 else 100.0 * max(counts) / mean
