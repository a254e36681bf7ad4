"""Service below the knee: due time to first token, 90th percentile over
the requests due in the window (s); its spread over seeds is too wide to
bound at this window's ~29 requests, so it is recorded, not judged."""
from bench.lib.harness import pct


def read(run):
    return pct(run.ttft_s(), 90) if run.reqs else None
