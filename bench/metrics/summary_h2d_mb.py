"""summary_h2d_mb: bytes the summaries send to the device
(``summary.h2d_bytes``) per leaf flush or merge-reduce in the window, in
MB; none where the program does not count them."""


def read(run):
    if not any(k.split("{", 1)[0] == "summary.h2d_bytes"
               for k in run.samples["obs1"]["counters"]):
        return None
    units = run.counter("tree.leaf_flushes") + run.counter("tree.merges")
    if units <= 0:
        return None
    return run.counter("summary.h2d_bytes") / units / 1e6
