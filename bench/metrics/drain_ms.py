"""drain_ms: mean ``score.drain`` span per scheduler tick in the window."""
from bench.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "phase.score.drain")
