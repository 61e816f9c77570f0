"""device.idle_pct [%]: share of the measured window in which no
operation ran on the device, averaged over the cell's chips."""

import devtrace


def read(trace):
    if not trace.devices or trace.window_ns <= 0:
        return None
    lo, hi = trace.window
    idle = [1.0 - devtrace.busy_ns(d, lo, hi) / trace.window_ns
            for d in trace.devices]
    return 100.0 * sum(idle) / len(idle)
