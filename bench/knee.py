#!/usr/bin/env python3
"""Sweep of offered score load, to find a configuration's knee once.

    python3 bench/knee.py --workload kdd99.score --seed 5 --seconds 5 \\
        --rates 5000 10000 20000 40000

One set-up, then one open-loop window per rate (the cell's mix at that
rate instead of its share of the knee).  For each rate it prints the rows
offered and completed per second, the requests not answered, the
scheduler's deepest queue, the latency quartiles and how late the
generator ran.  The knee is the highest rate at which the completed rows
keep up with the offered and the queue stays below its bound (under
``shed`` nothing is shed); it goes into the configuration's
``knee_rows_per_s``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as harness  # noqa: E402
from bench.metrics_common import answered  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, args.seed)
    harness.start_jax(cell.chips, require_chip=True)
    sys.path.insert(0, str(harness.ROOT / "src"))
    cell.setup()
    sched = cell.session.serve()
    for rate in args.rates:
        sched.peak_depth = 0
        s = cell.window(args.seconds, rate=rate)
        reqs = s["requests"]
        ok = [r for r in reqs if answered(r)]
        lat = np.array([r["done"] - r["due"] for r in ok]) * 1e3
        late = np.array([r["submit"] - r["due"] for r in reqs
                         if r["submit"] is not None]) * 1e3
        rows = sum(r["ids"].size for r in ok)
        print(json.dumps({
            "offered_rows_per_s": rate,
            "completed_rows_per_s": rows / args.seconds,
            "requests": len(reqs), "failed": len(reqs) - len(ok),
            "peak_queue": sched.peak_depth,
            "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
            "p99_ms": float(np.percentile(lat, 99)) if lat.size else None,
            "late_p99_ms": float(np.percentile(late, 99))
            if late.size else None}), flush=True)
    cell.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
