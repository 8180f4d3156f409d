"""Arithmetic shared by several metric readers."""
from __future__ import annotations

import numpy as np


def mean_span_ms(run, histogram: str):
    """Mean of a program span in the window, from its phase histogram."""
    count, total = run.hist(histogram)
    return 1e3 * total / count if count else None


def answered(req: dict) -> bool:
    """Every row of the request got its answer (none shed or failed)."""
    a = req["answers"]
    return (req["done"] is not None and a is not None
            and all(isinstance(r, tuple) for r in a))


def latencies(run) -> list[float]:
    """Due time to last result, in seconds, of the requests due in the
    window that were answered in full (shed or failed ones count in
    ``failed``)."""
    return [r["done"] - r["due"] for r in run.samples.get("requests", [])
            if answered(r)]


def latency_percentile(run, q: float):
    lat = latencies(run)
    return float(np.percentile(lat, q)) * 1e3 if lat else None
