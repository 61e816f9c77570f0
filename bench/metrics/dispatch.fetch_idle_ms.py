"""dispatch.fetch_idle_ms [ms]: device idle time inside
``repro.search.fetch`` per study, averaged over the cell's chips: what
the blocking device->host copy of each launch's results costs the
device."""

import spans


def read(trace):
    if not trace.devices:
        return None
    idle = [spans.idle_ns(trace, d, "repro.search.fetch") for d in trace.devices]
    if idle[0] is None:
        return None
    return spans.per_study_ms(trace, sum(idle) / len(idle))
