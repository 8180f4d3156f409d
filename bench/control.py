#!/usr/bin/env python3
"""Readings for the limits of the correctness check, on the chip.

    python3 bench/control.py --workload kdd99.ingest --seconds 3 \\
        --seeds 101 102 103 ... --control 3 --plant lloyd1

For each seed, one process sets the cell up and runs a short window at the
cell's own load, then prints every compared number of the program and, for
the first ``--control`` seeds, of the control: the reference in the
program's place with its matmul at ``Precision.HIGH``
(``reference.control_nearest``).  With ``--plant``, those seeds then run
one more window with a fault planted in the program's refresh and print
its numbers too: ``lloyd1`` cuts the k-means-- Lloyd loop to one
iteration, ``seeding`` to none (the seeding is installed).  The limits in
``configs/<name>.json`` are set from these readings: above the largest
sound reading, below the smallest control or fault reading.  One JSON line
per seed on standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as harness  # noqa: E402

PLANTS = {"lloyd1": 1, "seeding": 0}     # Lloyd iterations left


@contextlib.contextmanager
def planted(name: str):
    """The program's second-level fit with its Lloyd loop cut short."""
    import repro.stream.service as svc
    import repro.stream.sharded as sh
    orig = svc.fit_model

    def fit_model(*a, **kw):
        return orig(*a, **{**kw, "iters": PLANTS[name]})
    svc.fit_model = sh.fit_model = fit_model
    try:
        yield
    finally:
        svc.fit_model = sh.fit_model = orig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--plant", choices=sorted(PLANTS), default=None)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell0 = harness.Cell(args.workload, args.seeds[0], spec=spec)
    harness.start_jax(cell0.chips, require_chip=True)
    sys.path.insert(0, str(harness.ROOT / "src"))
    for i, seed in enumerate(args.seeds):
        cell = harness.Cell(args.workload, seed, spec=spec)
        cell.setup()
        cell.window(args.seconds)
        line = {"seed": seed, "program": cell.numbers()}
        if i < args.control:
            line["control"] = cell.numbers(control=True)
            if args.plant and cell.mix.get("ingest"):
                with planted(args.plant):
                    # the first refresh with the fault compiles: let it
                    # happen before the window
                    period = int(cell.config["pipeline"]["refresh_every"])
                    cell.session.ingest(cell.stream.take(period))
                    cell.window(args.seconds)
                    line[args.plant] = cell.numbers()
        cell.close()
        print(json.dumps(line), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
