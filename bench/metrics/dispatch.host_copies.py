"""dispatch.host_copies [count]: device arrays the fetches read back to
the host per study, the sum of ``repro.search.fetch``'s ``copies``
argument over the window, per study. ``None`` on a program whose fetch
spans carry no such argument."""

import spans


def read(trace):
    n = spans.arg_total(trace, "repro.search.fetch", "copies")
    if n is None or not trace.studies:
        return None
    return n / len(trace.studies)
