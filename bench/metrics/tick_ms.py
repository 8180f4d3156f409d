"""tick_ms: mean ``serve.tick`` span (a scheduler tick, from the pop of
its batch to its last ticket resolved) in the window."""
from bench.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "phase.serve.tick")
