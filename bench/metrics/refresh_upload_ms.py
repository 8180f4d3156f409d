"""refresh_upload_ms: mean ``refresh.upload`` span (the root a refresh
fits on, from its dispatch to the device until resident there) in the
window."""
from bench.metrics_common import mean_span_ms


def read(run):
    return mean_span_ms(run, "phase.refresh.upload")
