"""Pallas TPU kernel: fused Lloyd step (assign + weighted accumulate).

Second-level k-means-- iterates Lloyd steps on the summary; on TPU the
naive version is two scatter-adds (bad: serialized on the scalar core) plus
an HBM-resident (n, k) distance matrix.  This kernel instead:

  grid = (n_tiles,)  sequential ("arbitrary") so the (k, d) accumulator
  output blocks are revisited and stay resident in VMEM across the sweep
  (constant index_map), initialized on the first step.

  per tile:  dist  = x2 + c2 - 2 x @ cT      (MXU)
             aloc  = argmin(dist, axis=1)
             onehot(bn, k) = iota_k == aloc  (VPU compare)
             sums   += (onehot * w)^T @ x    (MXU again — the scatter-add
                                              becomes a matmul)
             counts += column-sum(onehot * w)

k is kept whole in one block (k <= ~2048 for the paper's workloads: the
coordinator clusters k=O(100) centers out of the summary).  Padded center
rows sit at 1e15 so they never win an argmin; padded x rows carry weight 0
so they contribute nothing.

Metrics: l2sq / l2 (assignment distance; the update is the weighted mean in
both cases — k-means-- is a means algorithm).  l1 has no kernel here (no
MXU win to be had): auto selection serves it with the blocked backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pdist.ref import F32

_BIG = 3.0e38
_PAD_COORD = 1.0e15
# (BN, K) f32 elements per row tile.  The tile's distance and one-hot blocks
# plus the f32-precision matmul temporaries fit a v5e kernel's VMEM at
# 2^20 (BN=256 at K=4096) and overflow it at 2^21, so BN shrinks as K grows.
_MAX_TILE = 1 << 20


def _kernel(x_ref, w_ref, c_ref, sums_ref, counts_ref, assign_ref, dist_ref,
            *, sqrt: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...].astype(jnp.float32)            # (BN, d)
    w = w_ref[...].astype(jnp.float32)            # (BN, 1)
    c = c_ref[...].astype(jnp.float32)            # (K, d)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())), precision=F32,
                             preferred_element_type=jnp.float32)
    dist = jnp.maximum(x2 + c2[None, :] - 2.0 * xc, 0.0)   # (BN, K)
    if sqrt:
        dist = jnp.sqrt(dist)
    aloc = jnp.argmin(dist, axis=1).astype(jnp.int32)      # (BN,)
    dloc = jnp.min(dist, axis=1, keepdims=True)            # (BN, 1)
    assign_ref[...] = aloc[:, None]
    dist_ref[...] = dloc

    k = c.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], k), 1)
    onehot = (iota == aloc[:, None]).astype(jnp.float32) * w   # (BN, K)
    # scatter-add as MXU matmul: (K, BN) @ (BN, d)
    sums_ref[...] += jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                                         precision=F32,
                                         preferred_element_type=jnp.float32)
    counts_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).T  # (K, 1)


def _pad_to(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


@functools.partial(jax.jit, static_argnames=("metric", "bn", "interpret"))
def lloyd_step_pallas(
    x: jnp.ndarray,
    w: jnp.ndarray,
    c: jnp.ndarray,
    *,
    metric: str = "l2sq",
    bn: int = 1024,
    interpret: bool = False,
):
    """``interpret=True`` runs the Pallas interpreter (off-chip tests only);
    otherwise the kernel compiles for the TPU or fails."""
    if metric not in ("l2sq", "l2"):
        raise ValueError("lloyd kernel supports l2sq/l2; l1 is served by "
                         "the blocked backend")
    n, d = x.shape
    k = c.shape[0]
    kp = _pad_to(k, 128)
    bn = max(8, min(bn, _pad_to(n, 8), _MAX_TILE // kp // 8 * 8))
    np_, dp = _pad_to(n, bn), _pad_to(d, 128)
    xp = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    wp = jnp.pad(w.reshape(-1, 1), ((0, np_ - n), (0, 0)))
    cp = jnp.pad(c, ((0, kp - k), (0, dp - d)), constant_values=_PAD_COORD)
    # keep genuine feature columns zero-padded (pad value applies everywhere,
    # so re-zero the d-padding for real rows):
    cp = cp.at[:k, d:].set(0.0)

    grid = (np_ // bn,)
    sums, counts, assign, dist = pl.pallas_call(
        functools.partial(_kernel, sqrt=(metric == "l2")),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((kp, dp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kp, dp), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, wp, cp)
    return sums[:k, :d], counts[:k, 0], assign[:n, 0], dist[:n, 0]
