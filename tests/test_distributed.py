"""Algorithm 3 (coordinator model): quality, communication accounting,
partition modes; multi-device via an 8-device subprocess."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (distributed_cluster, local_budget, simulate_coordinator,
                        sites_mesh)
from repro.core.metrics import outlier_scores
from repro.data.synthetic import gauss, partition


def test_local_budget():
    assert local_budget(100, 10, "random") == 20
    assert local_budget(100, 10, "adversarial") == 100
    assert local_budget(5, 100, "random") == 1


def test_simulate_quality_and_comm():
    x, out_ids = gauss(n_centers=20, per_center=500, t=200, sigma=0.1, seed=2)
    parts, gids = partition(x, 5, "random", seed=0, outlier_ids=out_ids)
    res = simulate_coordinator(parts, jax.random.key(0), k=20, t=200)
    conc = np.concatenate(gids)
    sc = outlier_scores(out_ids, conc[res["summary_ids"]], conc[res["outlier_ids"]])
    assert sc.pre_recall >= 0.95
    assert sc.recall >= 0.8 and sc.precision >= 0.8
    # one-round comm == number of summary records
    assert res["comm_records"] == len(res["summary_ids"])


def test_adversarial_partition_larger_budget_still_works():
    x, out_ids = gauss(n_centers=10, per_center=300, t=60, sigma=0.1, seed=4)
    parts, gids = partition(x, 4, "adversarial", seed=0, outlier_ids=out_ids)
    res = simulate_coordinator(parts, jax.random.key(0), k=10, t=60,
                               partition="adversarial")
    conc = np.concatenate(gids)
    sc = outlier_scores(out_ids, conc[res["summary_ids"]], conc[res["outlier_ids"]])
    assert sc.pre_recall >= 0.9  # all outliers on one site must still surface
    assert sc.recall >= 0.7


def test_shardmap_single_device_matches_simulate_quality():
    x, out_ids = gauss(n_centers=10, per_center=400, t=80, sigma=0.1, seed=6)
    mesh = sites_mesh(1)
    res = distributed_cluster(jnp.asarray(x)[None], jax.random.key(0), mesh,
                              k=10, t=80)
    oi = np.asarray(res.outlier_ids)
    oi = oi[oi >= 0]
    si = np.asarray(res.summary_ids)
    sc = outlier_scores(out_ids, si[si >= 0], oi)
    assert sc.pre_recall >= 0.9
    assert sc.recall >= 0.75


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import distributed_cluster, sites_mesh
    from repro.core.metrics import outlier_scores
    from repro.data.synthetic import gauss, partition

    x, out_ids = gauss(n_centers=10, per_center=400, t=160, sigma=0.1, seed=1)
    parts, gids = partition(x, 8, "random", seed=3, outlier_ids=out_ids)
    xs = jnp.asarray(np.stack(parts))
    mesh = sites_mesh(8)
    res = distributed_cluster(xs, jax.random.key(0), mesh, k=10, t=160)
    conc = np.concatenate(gids)
    oi = np.asarray(res.outlier_ids); oi = conc[oi[oi >= 0]]
    si = np.asarray(res.summary_ids); si = conc[si[si >= 0]]
    sc = outlier_scores(out_ids, si, oi)
    print(json.dumps({"pre": sc.pre_recall, "rec": sc.recall,
                      "prec": sc.precision, "comm": float(res.comm_records)}))
""")


@pytest.mark.slow
def test_shardmap_eight_sites_subprocess():
    """Real multi-device shard_map run: 8 sites, one all_gather round."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["pre"] >= 0.9
    assert res["rec"] >= 0.75
    assert res["comm"] > 0
