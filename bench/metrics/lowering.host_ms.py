"""lowering.host_ms [ms]: self time of ``repro.lower`` per study, the
host turning the study's workload into its GEMM stream
(``WorkloadSpec.resolve``; ``network.lower_network`` for a network)."""

import spans


def read(trace):
    return spans.per_study_ms(trace, spans.self_ns(trace, "repro.lower"))
