"""score_p50_ms: median of the score requests due in the window, each from
its due time to its client seeing its last row's result."""
from bench.metrics_common import latency_percentile


def read(run):
    return latency_percentile(run, 50)
