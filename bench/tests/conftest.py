"""CPU tests of the benchmark (run with ``python -m pytest bench/tests``).

The harness runs here with ``require_chip=False`` at tiny sizes: the
same set-up, window and check as on the chip, on the CPU backend.
"""
import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_DEFAULT_PRNG_IMPL", "threefry2x32")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


def tiny_config(name: str) -> dict:
    """A configuration file shrunk to a size the CPU runs in seconds:
    the same shapes (d, k, metric, topology), a 20,000-row population."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg = copy.deepcopy(cfg)
    n = 20_000
    cfg["population"]["n"] = n
    if "planted" in cfg["population"]:
        cfg["population"]["planted"] = 20
    p = cfg["pipeline"]
    p.update(leaf_size=2048, window=n // 2,
             refresh_every=8192 if p["topology"] == "sharded" else 4096)
    if p["k"] > 20:
        p["k"] = 20
    p["t"] = min(p["t"], 200)
    cfg.update(warmup_batch=1024, warmup_points=n // 2, warmup_max_points=n)
    if "knee_rows_per_s" in cfg:
        cfg["knee_rows_per_s"] = 4000
    return cfg


def spec_with_four_sites() -> dict:
    """BENCHMARK.json with the four-site cell that is not proven on the
    chip yet (``configs/kdd99-4site.json``), for the CPU tests of the
    sharded path on four virtual devices."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if any(w["name"] == "kdd99-4site.ingest" for w in spec["workloads"]):
        return spec
    spec["configs"].append({"name": "kdd99-4site",
                            "file": "bench/configs/kdd99-4site.json"})
    spec["workloads"].append({"name": "kdd99-4site.ingest",
                              "config": "kdd99-4site",
                              "traffic": "ingest_closed", "chips": 4})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "kdd99.ingest" in m.get("workloads", []):
            m["workloads"].append("kdd99-4site.ingest")
    return spec
