"""lowering.gemms [count]: unique GEMMs a study searches and prices,
the sum of ``repro.lower``'s ``gemms`` argument (the rows of the
resolved stream) over the window, per study. ``None`` on a program
whose lowering span carries no such argument."""

import spans


def read(trace):
    n = spans.arg_total(trace, "repro.lower", "gemms")
    if n is None or not trace.studies:
        return None
    return n / len(trace.studies)
