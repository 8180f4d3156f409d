"""With the timed path broken underneath, the run reports ``correct``
false: a refresh that leaves its state unchanged, a refresh whose Lloyd
loop is cut to one iteration (SUSY) or to none (KDD, where one iteration
reaches the fixed point), half of each batch left out, an answer
altered where it is produced, and (four virtual devices) the exchange
between the sites left out.  The look for a chip is skipped; everything
else is the run as the chip makes it."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run as harness
from conftest import ROOT, tiny_config
from test_traffic import run_tiny


def stale_refresh(mp):
    """The refresh keeps the model it had: its state returns unchanged."""
    from repro.stream.service import ServingFrontEnd
    orig = ServingFrontEnd._install

    def install(self, model, fit_s, records):
        orig(self, self.model if self.model is not None else model, fit_s,
             records)
    mp.setattr(ServingFrontEnd, "_install", install)


def half_of_each_batch(mp):
    """The tree takes in the first half of every batch it is given."""
    from repro.stream.tree import StreamTree
    orig = StreamTree.ingest

    def ingest(self, points, weights=None):
        points = np.asarray(points)
        keep = (points.shape[0] + 1) // 2
        orig(self, points[:keep],
             None if weights is None else np.asarray(weights)[:keep])
    mp.setattr(StreamTree, "ingest", ingest)


def lloyd_cut_to(iters):
    def fault(mp):
        import repro.stream.service as svc
        orig = svc.fit_model

        def fit_model(*a, **kw):
            return orig(*a, **{**kw, "iters": iters})
        mp.setattr(svc, "fit_model", fit_model)
    return fault


# the refresh's k-means-- runs one Lloyd iteration instead of its 25, or
# none (its seeding is installed)
lloyd_cut_to_one, seeding_only = lloyd_cut_to(1), lloyd_cut_to(0)


def altered_threshold(mp):
    """The refreshed model's threshold is off by one part in 10^4."""
    import repro.stream.service as svc
    orig = svc.fit_model

    def fit_model(*a, **kw):
        m = orig(*a, **kw)
        return m._replace(threshold=m.threshold * (1 + 1e-4))
    mp.setattr(svc, "fit_model", fit_model)


def altered_distance(mp):
    """The served distance is off by one part in 10^4."""
    import repro.stream.service as svc
    orig = svc._score_batch

    def score_batch(*a, **kw):
        dist, amin, score = orig(*a, **kw)
        return dist * (1 + 1e-4), amin, score
    mp.setattr(svc, "_score_batch", score_batch)


def half_of_each_tick(mp):
    """Drain answers the first half of what it was asked."""
    from repro.stream.service import ServingFrontEnd
    orig = ServingFrontEnd.drain

    def drain(self, max_requests=None):
        out = orig(self, max_requests)
        return out[:(len(out) + 1) // 2] if len(out) > 1 else out
    mp.setattr(ServingFrontEnd, "drain", drain)


@pytest.mark.parametrize("workload,config,fault,fails", [
    ("kdd99.ingest", "kdd99", stale_refresh, "thr_ulps"),
    ("kdd99.ingest", "kdd99", half_of_each_batch, "fed_gap"),
    ("kdd99.ingest", "kdd99", altered_threshold, "thr_ulps"),
    ("kdd99.ingest", "kdd99", seeding_only, "center_ulps"),
    ("susy.ingest", "susy", lloyd_cut_to_one, "center_ulps"),
    ("kdd99.score", "kdd99", altered_distance, "dist_ulps"),
    ("kdd99.score", "kdd99", half_of_each_tick, "missing"),
])
def test_fault_is_not_correct(monkeypatch, workload, config, fault, fails):
    cfg = tiny_config(config)
    sound = run_tiny(workload, cfg)
    assert sound["correct"], sound["checks"]
    orig_window = harness.Cell.window

    def window(self, *a, **kw):      # the fault lands as the window opens
        fault(monkeypatch)
        return orig_window(self, *a, **kw)
    monkeypatch.setattr(harness.Cell, "window", window)
    res = run_tiny(workload, cfg)
    assert not res["correct"]
    c = res["checks"][fails]
    assert c["value"] is not None and c["value"] > c["limit"], res["checks"]


EXCHANGE = """
import json, sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {tests!r})
sys.path.insert(0, {src!r})
import repro.stream.sharded as sh
from conftest import spec_with_four_sites, tiny_config
from test_traffic import run_tiny
cfg, spec = tiny_config("kdd99-4site"), spec_with_four_sites()
out = {{"sound": run_tiny("kdd99-4site.ingest", cfg, spec=spec)["checks"]}}
sh.gather_sites = lambda triple: triple     # each site keeps its own root
res = run_tiny("kdd99-4site.ingest", cfg, spec=spec)
out["fault"], out["correct"] = res["checks"], res["correct"]
print(json.dumps(out))
"""


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE.format(root=str(ROOT), src=str(ROOT / "src"),
                           tests=str(ROOT / "bench" / "tests"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert all(c["value"] <= c["limit"] for c in got["sound"].values())
    assert not got["correct"]
    gap = got["fault"]["trained_gap"]
    assert gap["value"] > gap["limit"]
