"""score_p99_ms: 99th percentile over the score requests due in the
window, each from its due time to its client seeing its last row's
result."""
from bench.metrics_common import latency_percentile


def read(run):
    return latency_percentile(run, 99)
