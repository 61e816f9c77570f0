"""search.device_ms [ms]: device time per study, on the busiest of the
cell's chips. Every device operation of these studies belongs to the
jitted (R, C) search (``analytical._search_rc``): the rest of a study
runs in numpy on the host."""

import devtrace


def read(trace):
    if not trace.devices or not trace.studies:
        return None
    lo, hi = trace.window
    busiest = max(devtrace.busy_ns(d, lo, hi) for d in trace.devices)
    return busiest * 1e-6 / len(trace.studies)
