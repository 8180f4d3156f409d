"""leaf_flush_ms: mean ``ingest.leaf_flush`` span (leaf summarize) in the
window."""
from bench.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "phase.ingest.leaf_flush")
