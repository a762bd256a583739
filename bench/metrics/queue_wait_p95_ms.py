"""95th percentile of the wait between a request's due time and its
admission by the scheduler (the program's ``Request.admit_t``), over the
requests due in the window before the trace began (starting and stopping
the profiler stalls the loop)."""

from bench.harness import percentile


def read(run):
    v = percentile(run.record.get("queue_wait_s", []), 95)
    return None if v is None else 1000.0 * v
