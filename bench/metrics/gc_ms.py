"""gc_ms: the garbage collector's pauses of generation 1 and 2
(``runtime.gc`` spans) added up over the window, in ms; none where the
program times no collection."""


def read(run):
    if not any(k.split("{", 1)[0] == "phase.runtime.gc"
               for k in run.samples["obs1"]["histograms"]):
        return None
    _, total = run.hist("phase.runtime.gc")
    return 1e3 * total
