"""Each traffic mix drives one tiny window through ``Session`` on the CPU,
and the run's answers come out correct."""
import argparse
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run as harness
from conftest import ROOT, tiny_config


def run_tiny(workload, config, seconds=2.0, spec=None):
    args = argparse.Namespace(workload=workload, seed=2**31 + 17,
                              seconds=seconds, trace=0, trace_dir=None)
    return harness.run(args, require_chip=False, config=config, spec=spec)


@pytest.mark.parametrize("workload,config,metrics", [
    ("kdd99.ingest", "kdd99", {"ingest_pts_per_s", "refresh_s", "setup_s"}),
    ("kdd99.score", "kdd99", {"score_p50_ms", "setup_s"}),
    ("susy.ingest", "susy", {"ingest_pts_per_s", "refresh_s", "setup_s"}),
])
def test_mix_runs_one_tiny_window(workload, config, metrics):
    res = run_tiny(workload, tiny_config(config))
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == metrics
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


FOUR_SITES = """
import json, sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {tests!r})
from conftest import spec_with_four_sites, tiny_config
from test_traffic import run_tiny
res = run_tiny("kdd99-4site.ingest", tiny_config("kdd99-4site"),
               spec=spec_with_four_sites())
print(json.dumps({{"correct": res["correct"], "metrics": sorted(res["metrics"])}}))
"""


def test_four_sites_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_SITES.format(root=str(ROOT), tests=str(ROOT / "bench" /
                                                        "tests"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"]
    assert got["metrics"] == ["ingest_pts_per_s", "refresh_s", "setup_s"]


def test_every_seed_offers_the_same_work():
    from bench import data, loadgen
    mix = json.loads((ROOT / "bench" / "traffic" / "score_open.json")
                     .read_text())["score"]
    rate, seconds = 5600.0, 30.0

    def work(seed):
        plans = loadgen.score_schedule(mix, rate, seconds, 494021,
                                       data.rng_for(seed, 3))
        sizes = sorted(r["ids"].size for p in plans for r in p)
        gaps = np.sort([np.diff([0.0] + [r["due"] for r in p])
                        for p in plans])
        last = max(r["due"] for p in plans for r in p)
        return sizes, gaps, last, plans

    a_sizes, a_gaps, a_last, a = work(2**31 + 5)
    b_sizes, b_gaps, b_last, b = work(7)
    assert a_sizes == b_sizes
    np.testing.assert_allclose(a_gaps, b_gaps, rtol=0, atol=1e-9)
    assert max(a_last, b_last) < seconds
    assert abs(sum(a_sizes) / seconds / rate - 1) < 0.01
    assert [r["ids"].size for r in a[0]] != [r["ids"].size for r in b[0]]
