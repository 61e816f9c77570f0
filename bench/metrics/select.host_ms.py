"""select.host_ms [ms]: self time of ``repro.select`` per study: the
candidate fixed designs and the policy reduction of a schedule, the
tier argmin of the Fig. 7 sweep."""

import spans


def read(trace):
    return spans.per_study_ms(trace, spans.self_ns(trace, "repro.select"))
