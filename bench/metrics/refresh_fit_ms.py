"""refresh_fit_ms: mean ``refresh.fit`` span (second-level k-means--,
blocked until ready) in the window."""
from bench.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "phase.refresh.fit")
