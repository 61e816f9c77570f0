"""search.points [count]: points the (R, C) search is given per study,
the sum of ``repro.search``'s ``rows`` argument over the window. Beside
``search.device_ms`` it tells fewer points from cheaper ones."""

import spans


def read(trace):
    n = spans.arg_total(trace, "repro.search", "rows")
    if n is None or not trace.studies:
        return None
    return n / len(trace.studies)
