"""95th percentile of time to first token over every request due in the
window, counted from when the request was due; a refused or unfinished
request counts with the time it had waited when the run gave up.

Not an end-to-end metric: at 1.6 req/s a 51 s window holds about 82
requests, so the 95th percentile rests on the slowest four or five and
moves by 10-14% between seeds, more than half the largest bound allowed
(0.25)."""

from bench.harness import percentile


def read(run):
    v = percentile(run.record.get("ttft_s", []), 95)
    return None if v is None else 1000.0 * v
