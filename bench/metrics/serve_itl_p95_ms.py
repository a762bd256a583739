"""95th percentile of the gaps between consecutive output tokens of a
request, over every token produced inside the window."""

from bench.harness import percentile


def read(run):
    v = percentile(run.record.get("itl_s", []), 95)
    return None if v is None else 1000.0 * v
