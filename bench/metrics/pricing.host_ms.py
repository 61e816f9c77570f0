"""pricing.host_ms [ms]: self time of ``repro.price`` per study: the
bandwidth roofline, switching activities, area and power of each
evaluation pass (``engine._evaluate_block``)."""

import spans


def read(trace):
    return spans.per_study_ms(trace, spans.self_ns(trace, "repro.price"))
