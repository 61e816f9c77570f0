"""dispatch.host_ms [ms]: self time of ``repro.search`` per study, the
host's share of the chunked search dispatch (``engine._search_batch``):
input preparation and enqueueing each launch, without the fetch of its
results (``repro.search.fetch``)."""

import spans


def read(trace):
    return spans.per_study_ms(trace, spans.self_ns(trace, "repro.search"))
