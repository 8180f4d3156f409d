"""Multi-device Algorithm 3: one shard_map program over an 8-device mesh.

Each device is a site: it builds its Summary-Outliers summary locally, one
all_gather moves the summaries (the paper's single communication round),
and the replicated second level recovers centers + global outliers.

    PYTHONPATH=src python examples/distributed_outliers.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import distributed_cluster, sites_mesh  # noqa: E402
from repro.core.metrics import outlier_scores  # noqa: E402
from repro.data.synthetic import gauss, partition  # noqa: E402


def main():
    s = len(jax.devices())
    print(f"running on {s} devices (sites)")
    x, out_ids = gauss(n_centers=16, per_center=1500, sigma=0.1, t=320, seed=1)
    parts, gids = partition(x, s, "random", seed=3, outlier_ids=out_ids)
    xs = jnp.asarray(np.stack(parts))

    mesh = sites_mesh(s)
    res = distributed_cluster(xs, jax.random.key(0), mesh, k=16, t=320)

    conc = np.concatenate(gids)
    oi = np.asarray(res.outlier_ids)
    reported = conc[oi[oi >= 0]]
    si = np.asarray(res.summary_ids)
    sc = outlier_scores(out_ids, conc[si[si >= 0]], reported)
    print(f"one-round communication: {float(res.comm_records):.0f} records")
    print(f"second-level cost (on summary): {float(res.cost):.4g}")
    print(f"outliers: preRec={sc.pre_recall:.4f} prec={sc.precision:.4f} "
          f"recall={sc.recall:.4f}")


if __name__ == "__main__":
    main()
