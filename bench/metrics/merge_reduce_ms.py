"""merge_reduce_ms: mean ``ingest.merge_reduce`` span in the window."""
from bench.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "phase.ingest.merge_reduce")
