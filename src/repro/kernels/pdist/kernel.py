"""Pallas TPU kernel: fused min-distance + argmin (the paper's hot-spot).

The paper's Algorithm 1 spends all of its time computing d(x, S_i) for every
remaining point — an (n x m x d) problem with tiny m (= alpha*max{k, log n})
and small-to-moderate d.  A naive implementation materializes the (n, m)
distance matrix in HBM (n can be millions); this kernel never does:

  grid = (n_tiles, m_tiles)   -- m innermost ("arbitrary"), n "parallel"
  x tile  (BN, d)  in VMEM    -- revisited across the m loop
  c tile  (BM, d)  in VMEM
  dist tile = x2 + c2 - 2 * x @ c^T   (MXU matmul at f32 precision)
  running (min, argmin) held in the OUTPUT blocks, which pallas keeps
  resident in VMEM across the inner m loop (same index_map for all j).

Arithmetic intensity: 2*BN*BM*d FLOPs per (BN*d + BM*d) * 4 bytes moved,
i.e. ~2*BM FLOPs/byte for BM >= BN — MXU-bound for BM >= ~128, which is why
BM defaults to 128 and BN to 512 (8 sublane-tiles of f32).

Tie-breaking matches ref.py: strict `<` updates keep the earliest m-tile;
within a tile jnp.argmin returns the first minimum.

The l1 metric has no MXU form: the tile sums |x - c| one feature column
at a time, eight x rows at a time, against the centers passed transposed
(d, BM), and writes each (8, BM) block into a (BN, BM) VMEM scratch.  No
(BN, BM, d) block is ever live (a 512x128x128 f32 block is 32 MiB, more
VMEM than the compiler grants a v5e kernel).  Only the real feature
columns are visited; the lane padding of d is zero on both sides.

``interpret`` defaults to False: the kernel compiles for the TPU or fails.
The Pallas interpreter runs only where a caller asks for it by name (the
CPU parity tests, ``KernelPolicy(backend="pallas", interpret=True)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pdist.ref import F32

_BIG = 3.0e38  # python float: jnp scalars would be captured as kernel consts
_PAD_COORD = 1.0e15  # padded center rows sit absurdly far away
_L1_ROWS = 8  # x rows per l1 register block (one f32 sublane tile)


def l2_tile_dist(x_ref, c_ref, *, sqrt: bool):
    """(BN, BM) l2sq / l2 distances of one x tile against one center tile."""
    x = x_ref[...].astype(jnp.float32)           # (BN, d)
    c = c_ref[...].astype(jnp.float32)           # (BM, d)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)  # (BN, 1)
    c2 = jnp.sum(c * c, axis=-1)                 # (BM,)
    # MXU: (BN, d) @ (d, BM)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())), precision=F32,
                             preferred_element_type=jnp.float32)
    dist = jnp.maximum(x2 + c2[None, :] - 2.0 * xc, 0.0)  # (BN, BM)
    return jnp.sqrt(dist) if sqrt else dist


def l1_tile_dist(x_ref, ct_ref, acc_ref, *, d: int):
    """(BN, BM) l1 distances of one x tile (BN, dp) against one transposed
    center tile (dp, BM).  Eight rows at a time, the (8, BM) partial sum
    stays in registers while the real feature columns are added in; only
    lane offsets known at trace time are sliced (Mosaic refuses dynamic
    ones)."""
    rows = _L1_ROWS

    def block(r, carry):
        r0 = pl.multiple_of(r * rows, rows)
        xs = x_ref[pl.ds(r0, rows), :].astype(jnp.float32)    # (8, dp)
        acc = jnp.zeros((rows, acc_ref.shape[1]), jnp.float32)
        for f in range(d):
            acc += jnp.abs(xs[:, f:f + 1]
                           - ct_ref[f:f + 1, :].astype(jnp.float32))
        acc_ref[pl.ds(r0, rows), :] = acc
        return carry

    jax.lax.fori_loop(0, acc_ref.shape[0] // rows, block, 0)
    return acc_ref[...]


def fold_min(dist, j, *, bm: int, dmin_ref, amin_ref):
    """Fold one center tile's (min, argmin) into the running output blocks
    (strict ``<``: ties keep the earliest tile)."""
    dloc = jnp.min(dist, axis=1, keepdims=True)            # (BN, 1)
    aloc = jnp.argmin(dist, axis=1).astype(jnp.int32)[:, None] + j * bm
    better = dloc < dmin_ref[...]
    dmin_ref[...] = jnp.where(better, dloc, dmin_ref[...])
    amin_ref[...] = jnp.where(better, aloc, amin_ref[...])


def init_min(j, dmin_ref, amin_ref):
    @pl.when(j == 0)
    def _init():
        dmin_ref[...] = jnp.full_like(dmin_ref, _BIG)
        amin_ref[...] = jnp.zeros_like(amin_ref)


def _l2_kernel(x_ref, c_ref, dmin_ref, amin_ref, *, bm: int, sqrt: bool):
    j = pl.program_id(1)
    init_min(j, dmin_ref, amin_ref)
    fold_min(l2_tile_dist(x_ref, c_ref, sqrt=sqrt), j, bm=bm,
             dmin_ref=dmin_ref, amin_ref=amin_ref)


def _l1_kernel(x_ref, ct_ref, dmin_ref, amin_ref, acc_ref, *, bm: int, d: int):
    j = pl.program_id(1)
    init_min(j, dmin_ref, amin_ref)
    fold_min(l1_tile_dist(x_ref, ct_ref, acc_ref, d=d), j, bm=bm,
             dmin_ref=dmin_ref, amin_ref=amin_ref)


def _pad_to(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def pad_operands(x, c, metric: str, bn: int, bm: int):
    """Pad rows to the tiles and d to the 128 lanes; padded centers sit at
    ``_PAD_COORD`` so they never win.  Returns (xp, cp, bn, bm) with the
    centers transposed to (dp, mp) for l1."""
    if metric not in ("l2sq", "l2", "l1"):
        raise ValueError(f"unknown metric {metric!r}")
    n, d = x.shape
    m = c.shape[0]
    bn = min(bn, _pad_to(n, 8))
    bm = min(bm, _pad_to(m, 128))
    np_, mp, dp = _pad_to(n, bn), _pad_to(m, bm), _pad_to(d, 128)
    xp = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    # d-padding is zero for both operands, so it adds nothing to a distance
    cp = jnp.pad(jnp.pad(c, ((0, mp - m), (0, 0)),
                         constant_values=_PAD_COORD), ((0, 0), (0, dp - d)))
    if metric == "l1":
        cp = cp.T
    return xp, cp, bn, bm


def tile_specs(metric: str, bn: int, bm: int, dp: int):
    """(x BlockSpec, center BlockSpec) over the (n_tiles, m_tiles) grid."""
    x_spec = pl.BlockSpec((bn, dp), lambda i, j: (i, 0))
    if metric == "l1":
        return x_spec, pl.BlockSpec((dp, bm), lambda i, j: (0, j))
    return x_spec, pl.BlockSpec((bm, dp), lambda i, j: (j, 0))


@functools.partial(jax.jit, static_argnames=("metric", "bn", "bm", "interpret"))
def min_argmin_pallas(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    metric: str = "l2sq",
    bn: int = 512,
    bm: int = 128,
    interpret: bool = False,
):
    """Fused (min distance, argmin) — Pallas path. See module docstring."""
    n, d = x.shape
    xp, cp, bn, bm = pad_operands(x, c, metric, bn, bm)
    np_, dp = xp.shape
    mp = cp.shape[1] if metric == "l1" else cp.shape[0]
    if metric == "l1":
        kernel = functools.partial(_l1_kernel, bm=bm, d=d)
        scratch = [pltpu.VMEM((bn, bm), jnp.float32)]
    else:
        kernel = functools.partial(_l2_kernel, bm=bm, sqrt=(metric == "l2"))
        scratch = []
    dmin, amin = pl.pallas_call(
        kernel,
        grid=(np_ // bn, mp // bm),
        in_specs=list(tile_specs(metric, bn, bm, dp)),
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(xp, cp)
    return dmin[:n, 0], amin[:n, 0]
