"""thermal.host_ms [ms]: self time of ``repro.thermal`` per study: the
lumped tier temperatures (and the transient governor) of each
evaluation pass, and a schedule's governed layer replay."""

import spans


def read(trace):
    return spans.per_study_ms(trace, spans.self_ns(trace, "repro.thermal"))
