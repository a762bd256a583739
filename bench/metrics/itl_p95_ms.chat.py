"""95th percentile of the gaps between consecutive output tokens of a
request, over every token produced inside the window, in the chat cell.

Not an end-to-end metric there: the gaps fall in two groups, ticks of
pure decode and ticks that also carry a prefill chunk, and at this load
the 95th percentile sits where they meet, so it jumps between the two
from run to run of one seed."""

from bench.harness import percentile


def read(run):
    v = percentile(run.record.get("itl_s", []), 95)
    return None if v is None else 1000.0 * v
