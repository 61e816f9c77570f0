"""host.unspanned_ms [ms]: time per study inside ``bench.study`` that no
layer span covers (``spans.LAYERS``): spec parsing, grid building and
whatever else the front door does between the layers."""

import spans


def read(trace):
    return spans.per_study_ms(trace, spans.unspanned_ns(trace))
