"""Pallas TPU kernel: fused min-distance + argmin + outlier score.

The serving hot path (``repro.stream.service`` / ``repro.serve``) scores
every query batch as ``dist = d(x, nearest center); score = dist /
threshold`` — after PR 7 the scheduler coalesces many clients into one
micro-batch, but that batch still paid separate pdist / argmin / divide
work with the (n,) intermediates round-tripping through HBM between
steps.  This kernel is the pdist kernel (``repro.kernels.pdist.kernel``)
extended with the scoring epilogue, so one launch covers the whole read
path:

  grid = (n_tiles, m_tiles)  -- m innermost; the automatic Pallas grid
  pipeline double-buffers the HBM->VMEM DMA of the next x row tile while
  the current one computes, and the (tiny) center tiles stay VMEM-resident
  across the row loop.
  running (min, argmin) live in the output blocks (same index_map for all
  j); on the LAST center tile the score output is written in-register as
  dmin / max(threshold, 1e-30) — the distance never returns to HBM just
  to be divided.

The threshold is a (1, 1) block broadcast to every grid step.  The tile
distances, padding sentinels and running-min fold are the pdist kernel's
own helpers (``repro.kernels.pdist.kernel``), so metrics and tie-breaking
(strict ``<`` keeps the earliest center tile; ``jnp.argmin`` keeps the
first minimum within a tile) match it exactly, and the fused outputs
agree with the composed ``min_argmin`` + divide path within float
tolerance with bit-equal argmins (asserted in tests/test_dispatch.py).
Cosine is excluded for the same reason as pdist: a far-away padding
sentinel is a direction, not a distance, under a normalized metric.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pdist.kernel import (fold_min, init_min, l1_tile_dist,
                                        l2_tile_dist, pad_operands,
                                        tile_specs)

_EPS = 1e-30  # threshold guard, matches the composed serving path


def _score_epilogue(j, nm, thr_ref, dmin_ref, score_ref):
    @pl.when(j == nm - 1)
    def _score():
        thr = jnp.maximum(thr_ref[0, 0], _EPS)
        score_ref[...] = dmin_ref[...] / thr


def _l2_score_kernel(x_ref, c_ref, thr_ref, dmin_ref, amin_ref, score_ref,
                     *, bm: int, nm: int, sqrt: bool):
    j = pl.program_id(1)
    init_min(j, dmin_ref, amin_ref)
    fold_min(l2_tile_dist(x_ref, c_ref, sqrt=sqrt), j, bm=bm,
             dmin_ref=dmin_ref, amin_ref=amin_ref)
    _score_epilogue(j, nm, thr_ref, dmin_ref, score_ref)


def _l1_score_kernel(x_ref, ct_ref, thr_ref, dmin_ref, amin_ref, score_ref,
                     acc_ref, *, bm: int, nm: int, d: int):
    j = pl.program_id(1)
    init_min(j, dmin_ref, amin_ref)
    fold_min(l1_tile_dist(x_ref, ct_ref, acc_ref, d=d), j, bm=bm,
             dmin_ref=dmin_ref, amin_ref=amin_ref)
    _score_epilogue(j, nm, thr_ref, dmin_ref, score_ref)


@functools.partial(jax.jit,
                   static_argnames=("metric", "bn", "bm", "interpret"))
def score_pallas(
    x: jnp.ndarray,
    c: jnp.ndarray,
    threshold: jnp.ndarray,
    *,
    metric: str = "l2sq",
    bn: int = 512,
    bm: int = 128,
    interpret: bool = False,
):
    """Fused (min distance, argmin, dist/threshold) — Pallas path.
    ``interpret=True`` runs the Pallas interpreter (off-chip tests only)."""
    n, d = x.shape
    xp, cp, bn, bm = pad_operands(x, c, metric, bn, bm)
    np_, dp = xp.shape
    mp = cp.shape[1] if metric == "l1" else cp.shape[0]
    nm = mp // bm
    thr = jnp.reshape(threshold, (1, 1)).astype(jnp.float32)
    if metric == "l1":
        kernel = functools.partial(_l1_score_kernel, bm=bm, nm=nm, d=d)
        scratch = [pltpu.VMEM((bn, bm), jnp.float32)]
    else:
        kernel = functools.partial(_l2_score_kernel, bm=bm, nm=nm,
                                   sqrt=(metric == "l2"))
        scratch = []
    row = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    dmin, amin, score = pl.pallas_call(
        kernel,
        grid=(np_ // bn, nm),
        in_specs=[*tile_specs(metric, bn, bm, dp),
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, cp, thr)
    return dmin[:n, 0], amin[:n, 0], score[:n, 0]
