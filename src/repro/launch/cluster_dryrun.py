import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run the PAPER'S OWN JOB on the production pod: Algorithm 3 with one
site per chip (256 sites single-pod / 512 multi-pod), lowering the full
summary-construction + all_gather + second-level program and extracting the
same roofline terms as the LM cells.

This is the cell "most representative of the paper's technique": it shows
the technique's signature — per-site O(max{k,log n}·n) compute against ONE
all-gather of O(k log n + t/s) records — as a compute-vs-collective ratio
on real mesh geometry.

  PYTHONPATH=src python -m repro.launch.cluster_dryrun [--n-per-site 65536]
      [--k 100] [--t 131072] [--d 32] [--multi]
"""
import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.collective import sites_mesh
from repro.core.distributed import distributed_cluster
from repro.kernels.dispatch import KernelPolicy
from repro.launch.dryrun import HBM_BW, LINK_BW, PEAK_FLOPS, _jsonable
from repro.launch.hlo import analyze as analyze_hlo


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-per-site", type=int, default=65536)
    ap.add_argument("--k", type=int, default=100)
    ap.add_argument("--t", type=int, default=131072)  # ~0.8% of 16.7M points
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    s = 512 if args.multi else 256
    mesh = sites_mesh(s)
    n, d = args.n_per_site, args.d
    x_s = jax.ShapeDtypeStruct((s, n, d), jnp.float32)
    key_s = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)

    def job(x, key):
        return distributed_cluster(x, key, mesh, k=args.k, t=args.t,
                                   summary_alg="plain",
                                   policy=KernelPolicy(block_n=16384))

    t0 = time.time()
    lowered = jax.jit(job, in_shardings=(NamedSharding(mesh, P("sites")),
                                         None)).lower(x_s, key_s)
    compiled = lowered.compile()
    t_compile = time.time() - t0

    an = analyze_hlo(compiled.as_text())
    flops, bts, wire = an["flops"], an["hbm_bytes"], an["total_wire_bytes"]
    compute_s = flops / PEAK_FLOPS
    memory_s = bts / HBM_BW
    collective_s = wire / LINK_BW
    rec = {
        "arch": "cluster-job(paper)", "shape": f"s{s}_n{n}_k{args.k}_t{args.t}",
        "mesh": ("multi" if args.multi else "single"),
        "chips": s, "status": "ok", "compile_s": round(t_compile, 2),
        "hlo_flops": flops, "hlo_bytes": bts, "wire_bytes": wire,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": max((("compute", compute_s), ("memory", memory_s),
                           ("collective", collective_s)),
                          key=lambda kv: kv[1])[0],
        "collectives": an["collectives"],
    }
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    tag = f"cluster-job__{rec['shape']}__{rec['mesh']}"
    (out / f"{tag}.json").write_text(json.dumps(_jsonable(rec), indent=1))
    print(f"compiled in {t_compile:.1f}s on {s} sites")
    print(f"compute {compute_s:.4f}s  memory {memory_s:.4f}s  "
          f"collective {collective_s:.6f}s  -> {rec['bottleneck']}-bound")
    print({k: (v['count'], round(v['wire_bytes'] / 1e6, 2))
           for k, v in an["collectives"].items()})


if __name__ == "__main__":
    main()
