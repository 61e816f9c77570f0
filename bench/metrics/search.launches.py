"""search.launches [count]: program executions per study on the first
of the cell's chips (a sharded launch runs on every chip at once)."""


def read(trace):
    if not trace.devices or not trace.studies:
        return None
    lo, hi = trace.window
    n = sum(1 for s, e, _ in trace.devices[0].modules if lo <= s and e <= hi)
    return n / len(trace.studies)
