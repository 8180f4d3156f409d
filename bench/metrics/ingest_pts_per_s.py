"""ingest_pts_per_s: points accepted by ``Session.ingest`` in the window
over the window's seconds, the blocking cadence refreshes included."""


def read(run):
    ing = run.samples.get("ingest")
    if not ing or ing["elapsed"] <= 0:
        return None
    return ing["points"] / ing["elapsed"]
