"""95th percentile of the time a request waits in the engine's queue, in
ms: the ``queued_ms`` stat of the program's ``mcsa.engine.prefill`` spans
in the traced window (host clock from ``InferenceEngine.submit`` to the
start of its prefill; inclusive quantiles)."""
from bench.program_spans import of, p95, program_spans, stat


def read(facts):
    tr = program_spans(facts)
    if tr is None:
        return None
    return p95(stat(of(tr, "engine.prefill"), "queued_ms"))
