"""Engine admission: due time to the end of the tick that gave the request
a slot, 90th percentile over the requests due in the window (s)."""
from bench.lib.harness import pct


def read(run):
    return pct(run.queue_wait_s(), 90) if run.reqs else None
