"""queue_wait_ms: median ``serve.queue_wait`` span (admission to the
tick that takes the row) among those the flight recorder holds from the
window."""
import statistics


def read(run):
    spans = run.spans("serve.queue_wait")
    if not spans:
        return None
    return statistics.median(t1 - t0 for t0, t1 in spans) * 1e3
