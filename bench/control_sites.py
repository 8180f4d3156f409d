#!/usr/bin/env python3
"""Readings for the limits of a cell whose refresh is one ``shard_map``
program over its sites (``topology: "sharded"``, ``use_shard_map``), on
the chip.

    python3 bench/control_sites.py --workload kdd99-4site.ingest \\
        --seconds 30 --seeds 101 102 --plant seeding

``bench/control.py`` plants its fault in ``fit_model`` after the set-up,
but the sharded service traced ``fit_model`` into its cached program
during the warm-up, so the fault would never run there.  Here, for each
seed, one process sets the cell up, runs a window of ``--seconds`` at the
cell's own load, and prints what the window ingested and refreshed, every
compared number of the program, and of the control: the reference in the
program's place with its matmul at ``Precision.HIGH``
(``reference.control_nearest``).  With ``--plant`` (``control.PLANTS``),
the engine's cached program is then dropped with the fault in place, so
the next refresh traces the fault in; the planted program is warmed up as
the set-up warms the sound one (whole refresh periods until two in a row
trace nothing), and a window of ``--plant-seconds`` prints its numbers.
One JSON line per seed and window on standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import control  # noqa: E402
from bench import run as harness  # noqa: E402

WARM_PERIODS = 8      # refresh periods the planted warm-up may take


def window_line(cell, samples) -> dict:
    """What the window did, read as ``bench/run.py``'s metrics read it."""
    view = harness.Run(cell, 0.0, "", None)
    out = {"refreshes": len(samples["refreshes"]),
           "points": samples["ingest"]["points"]}
    for name in ("ingest_pts_per_s", "refresh_s"):
        out[name] = harness.reader(name).read(view)
    return out


def warm_planted(cell) -> int:
    """Whole refresh periods until two in a row trace nothing; returns
    how many ran."""
    period = int(cell.config["pipeline"]["refresh_every"])
    batch = int(cell.config["warmup_batch"])
    quiet = ran = 0
    while quiet < 2:
        if ran == WARM_PERIODS:
            raise harness.BenchError(
                f"the planted program still traced after {ran} periods")
        cell.compiles.arm()
        goal = cell.stream.fed + period
        while cell.stream.fed < goal:
            cell.session.ingest(cell.stream.take(batch))
        quiet = quiet + 1 if cell.compiles.disarm()["traced"] == 0 else 0
        ran += 1
    return ran


def readings(cell, seconds: float) -> dict:
    """The sound window's numbers and the control's, of a cell that is
    set up."""
    if not getattr(cell.session.engine.cfg, "use_shard_map", False):
        raise harness.BenchError(
            f"{cell.workload['name']} has no shard_map refresh; "
            f"bench/control.py reads its limits")
    samples = cell.window(seconds)
    return {"window": window_line(cell, samples),
            "program": cell.numbers(),
            "control": cell.numbers(control=True)}


def planted_readings(cell, plant: str, seconds: float) -> dict:
    """The numbers of a window with ``plant`` traced into the refresh."""
    with control.planted(plant):
        cell.session.engine._fit_program = None   # retrace with the fault
        warm = warm_planted(cell)
        cell.window(seconds)
        return {"warm_periods": warm, plant: cell.numbers()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--plant", choices=sorted(control.PLANTS), default=None)
    ap.add_argument("--plant-seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell0 = harness.Cell(args.workload, args.seeds[0], spec=spec)
    harness.start_jax(cell0.chips, require_chip=True)
    sys.path.insert(0, str(harness.ROOT / "src"))
    for seed in args.seeds:
        cell = harness.Cell(args.workload, seed, spec=spec)
        cell.setup()
        print(json.dumps({"seed": seed, **readings(cell, args.seconds)}),
              flush=True)
        if args.plant:
            print(json.dumps({"seed": seed, **planted_readings(
                cell, args.plant, args.plant_seconds)}), flush=True)
        cell.close()
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
