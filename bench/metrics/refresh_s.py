"""refresh_s: mean wall time of the refreshes completed in the window,
from trigger to install (host clock around the engine's refresh)."""


def read(run):
    lo, _ = run.window
    times = [t1 - t0 for t0, t1 in run.refreshes if t0 >= lo]
    return sum(times) / len(times) if times else None
