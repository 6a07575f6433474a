"""device_idle_share: 1 - (union of the device's op intervals / traced
window), in percent, averaged over the chips."""
from bench import trace_reduce


def read(run):
    if run.trace is None or not run.trace.chips():
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace)
                    / run.trace.window_s)
