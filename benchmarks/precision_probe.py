"""Matmul precision of the l2 distance form ``|x|^2 + |c|^2 - 2 x.c`` on
the device JAX finds, against float64 on the host.

    PYTHONPATH=src python3 benchmarks/precision_probe.py [--n 65536]

For each ``jax.lax.Precision`` (DEFAULT, HIGH, HIGHEST) it computes the
(n, 3) squared distances of KDD-like rows (``kdd_like``, d=34) to three
centers, once as an XLA matmul and once inside a Pallas kernel, and
reports the largest error in units of ``eps32 * (|x|^2 + |c|^2)``: the
unit of ``chip_smoke.DIST_TOL_ULPS`` (64).  It also runs the served
``min_argmin_pallas`` (which asks for ``repro.kernels.pdist.ref.F32``)
and counts its argmins that differ from float64.  A precision the
compiler refuses is reported as such.  Off a TPU the Pallas kernels run
in interpret mode, where every precision is exact f32.

Prints one line, ``PRECISION_PROBE {json}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from repro.data.synthetic import kdd_like  # noqa: E402
from repro.kernels.pdist.kernel import min_argmin_pallas  # noqa: E402

PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}
_BN = 512


def _dist_kernel(x_ref, c_ref, o_ref, *, precision):
    x, c = x_ref[...], c_ref[...]
    x2 = jnp.sum(x * x, -1, keepdims=True)
    c2 = jnp.sum(c * c, -1)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             precision=precision,
                             preferred_element_type=jnp.float32)
    o_ref[...] = jnp.maximum(x2 + c2[None, :] - 2.0 * xc, 0.0)


@functools.partial(jax.jit, static_argnames=("precision", "interpret"))
def pallas_dist(x, c, *, precision, interpret):
    """(n, m) squared distances in one Pallas kernel, n a multiple of 512,
    d and m at most 128."""
    (n, d), m = x.shape, c.shape[0]
    xp = jnp.pad(x, ((0, 0), (0, 128 - d)))
    cp = jnp.pad(c, ((0, 128 - m), (0, 128 - d)))
    out = pl.pallas_call(
        functools.partial(_dist_kernel, precision=precision),
        grid=(n // _BN,),
        in_specs=[pl.BlockSpec((_BN, 128), lambda i: (i, 0)),
                  pl.BlockSpec((128, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((_BN, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 128), jnp.float32),
        interpret=interpret)(xp, cp)
    return out[:, :m]


@functools.partial(jax.jit, static_argnames=("precision",))
def xla_dist(x, c, *, precision):
    xc = jnp.matmul(x, c.T, precision=precision)
    return jnp.maximum((x * x).sum(-1)[:, None] + (c * c).sum(-1)[None]
                       - 2.0 * xc, 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=65_536,
                    help="rows (a multiple of 512)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    x, _ = kdd_like(n=args.n, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    c = (x[rng.choice(len(x), 3, replace=False)]
         + rng.normal(0, 0.1, (3, x.shape[1])).astype(np.float32))
    x64, c64 = x.astype(np.float64), c.astype(np.float64)
    ref = ((x64[:, None, :] - c64[None]) ** 2).sum(-1)
    scale = (x64 ** 2).sum(1)[:, None] + (c64 ** 2).sum(1)[None]
    eps = float(np.finfo(np.float32).eps)

    def err(dist):
        return float((np.abs(np.asarray(dist, np.float64) - ref)
                      / scale).max() / eps)

    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    out = {"device": dev.device_kind, "n": args.n, "unit":
           "max |d - d64| / (eps32 * (|x|^2 + |c|^2))"}
    xj, cj = jnp.asarray(x), jnp.asarray(c)
    for name, p in PRECISIONS.items():
        out[f"xla_{name}"] = err(xla_dist(xj, cj, precision=p))
        try:
            out[f"pallas_{name}"] = err(pallas_dist(
                xj, cj, precision=p, interpret=interpret))
        except Exception as e:   # a precision Mosaic does not lower
            out[f"pallas_{name}"] = "refused: " + str(e).splitlines()[0][:200]
    dk, ak = min_argmin_pallas(xj, cj, interpret=interpret)
    out["kernel_min"] = float((np.abs(np.asarray(dk, np.float64)
                                      - ref.min(1)) / scale.min(1)).max()
                              / eps)
    out["kernel_argmin_mismatch"] = int(
        (np.asarray(ak) != ref.argmin(1)).sum())
    print("PRECISION_PROBE " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
