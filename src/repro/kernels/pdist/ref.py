"""Pure-jnp oracle for the fused min-distance + argmin primitive.

``min_argmin_ref(x, c, metric)`` computes, for every row of ``x``, the
distance to the nearest row of ``c`` and the index of that row.  This is the
compute hot-spot of the paper's Algorithm 1 (Summary-Outliers): every round
computes d(x, S_i) for all remaining points.  The Pallas kernel in
``kernel.py`` must match this oracle bit-for-bit up to float tolerance
(ties broken toward the smaller index in both).

Metrics:
  * ``l2sq``   — squared Euclidean distance (used for (k,t)-means).
  * ``l2``     — Euclidean distance (used for (k,t)-median).
  * ``l1``     — Manhattan distance (the paper notes any metric with a
                 distance oracle works).
  * ``cosine`` — 1 - cos(x, c) in [0, 2]; rows are normalized internally so
                 callers may pass unnormalized data.  Served by the blocked
                 and ref backends only (the Pallas kernel's far-away padding
                 sentinel is meaningless under a direction-only metric, so
                 its capability predicate excludes cosine and auto selection
                 routes around it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

METRICS = ("l2sq", "l2", "l1", "cosine")

# f32 matmuls on a TPU default to one bf16 pass (the MXU and Mosaic alike):
# measured on a v5e, x2 + c2 - 2 x.c then errs by ~1.4e4 f32 ulps of
# |x|^2 + |c|^2, enough to move argmins and the score > 1 boundary.  Every
# distance and center-update matmul on the served path asks for full f32.
# On the CPU this is a no-op.  benchmarks/precision_probe.py measures each
# precision on the device it runs on.
F32 = jax.lax.Precision.HIGHEST

# metrics the Pallas pdist kernel implements (see kernel.py); keep in sync
PALLAS_METRICS = ("l2sq", "l2", "l1")


def _unit(v: jnp.ndarray) -> jnp.ndarray:
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-30)


def pairwise(x: jnp.ndarray, c: jnp.ndarray, metric: str = "l2sq") -> jnp.ndarray:
    """Full (n, m) pairwise distance matrix. O(n*m*d) memory-free form for
    l2*, O(n*m*d) materialized for l1 — oracle only, not the production path."""
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}")
    if metric == "l1":
        return jnp.abs(x[:, None, :] - c[None, :, :]).sum(-1)
    if metric == "cosine":
        sim = jnp.matmul(_unit(x), _unit(c).T, precision=F32)
        return jnp.clip(1.0 - sim, 0.0, 2.0)
    x2 = (x * x).sum(-1)
    c2 = (c * c).sum(-1)
    d2 = x2[:, None] + c2[None, :] - 2.0 * jnp.matmul(x, c.T, precision=F32)
    d2 = jnp.maximum(d2, 0.0)
    return d2 if metric == "l2sq" else jnp.sqrt(d2)


def min_argmin_ref(x: jnp.ndarray, c: jnp.ndarray, metric: str = "l2sq"):
    """(min distance, argmin index) per row of x. Ties -> smallest index."""
    d = pairwise(x, c, metric)
    return d.min(axis=1), d.argmin(axis=1).astype(jnp.int32)
