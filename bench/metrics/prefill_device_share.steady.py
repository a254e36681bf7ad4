"""Model step: device time of the prefill programs (the ``prefill``,
``splice_pages`` and ``prefill_chunk`` modules on the device's XLA Modules
line) over device busy time in the traced slice (%)."""
from bench.lib import spans as SP


def read(run):
    if run.engine_trace is None:
        return None
    lo, hi = run.trace_window
    return SP.module_share(run.engine_trace.modules, run.trace.devices,
                           lo, hi)
