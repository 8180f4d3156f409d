"""device_idle.ingest: share of the window with no operation on the
device, from the trace, in a score cell (percent)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]
