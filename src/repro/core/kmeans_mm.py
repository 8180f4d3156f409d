"""k-means-- (Chawla & Gionis 2013), weighted, as the second-level clusterer.

Lloyd-style alternation that jointly optimizes k centers and t outliers:
each iteration assigns points to nearest centers, marks the farthest mass
(total weight <= t) as outliers, and recomputes centers from the inliers.
The paper adopts exactly this as the coordinator-side algorithm: it returns
exactly k centers + t outliers and works well in practice (no worst-case
guarantee, as they note).

This version is weighted so it can consume summary points: a summary record
(q, w_q) acts as w_q coincident points.  Outlier selection is the natural
weighted generalization — greedily take farthest records while the
cumulative weight stays <= t.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.kmeans_pp import kmeanspp_seed
from repro.kernels.dispatch import KernelPolicy, resolve_policy
from repro.kernels.lloyd.ops import accumulate_by_assignment, lloyd_step
from repro.kernels.pdist.ops import min_argmin


def seed_trials(k: int) -> int:
    """Candidates per greedy k-means++ step: 2 + ln k, the usual choice."""
    return 2 + int(math.log(k))


class OutlierClustering(NamedTuple):
    centers: jnp.ndarray       # (k, d)
    assignment: jnp.ndarray    # (n,) int32 — nearest-center index
    outlier: jnp.ndarray       # (n,) bool
    cost: jnp.ndarray          # () weighted objective over inliers
    distances: jnp.ndarray     # (n,) distance to assigned center


def _mark_outliers(dist, w_eff, t):
    """Greedy farthest-first: True for records whose cumulative weight
    (in decreasing-distance order) stays within the budget t."""
    order = jnp.argsort(-dist)
    cumw = jnp.cumsum(w_eff[order])
    out_sorted = (cumw <= t) & (w_eff[order] > 0)
    return jnp.zeros_like(out_sorted).at[order].set(out_sorted)


def kmeans_minus_minus(
    points: jnp.ndarray,
    weights: jnp.ndarray,
    valid: jnp.ndarray,
    key: jax.Array,
    *,
    k: int,
    t: float,
    iters: int = 25,
    metric: str = "l2sq",
    policy: Optional[KernelPolicy] = None,
    init_centers: Optional[jnp.ndarray] = None,
    block_n: Optional[int] = None,      # removed alias: raises TypeError
    use_pallas: Optional[bool] = None,  # removed alias: raises TypeError
) -> OutlierClustering:
    """``init_centers`` (k, d): warm-start the Lloyd loop from these
    centers instead of k-means++ seeding (``key`` is then unused) — the
    incremental-refresh path re-fits from the previous model when little
    of the root changed.  ``None`` (default) seeds as usual and is
    bit-identical to every prior release."""
    policy = resolve_policy(policy, use_pallas=use_pallas, block_n=block_n,
                            caller="kmeans_minus_minus")
    if init_centers is None:
        return _kmeans_minus_minus(points, weights, valid, key, k=k, t=t,
                                   iters=iters, metric=metric, policy=policy)
    init_centers = jnp.asarray(init_centers, jnp.float32)
    if init_centers.shape != (k, points.shape[1]):
        raise ValueError(
            f"init_centers must have shape ({k}, {points.shape[1]}), "
            f"got {tuple(init_centers.shape)}")
    return _kmeans_minus_minus_warm(points, weights, valid, init_centers,
                                    t=t, iters=iters, metric=metric,
                                    policy=policy)


@functools.partial(jax.jit,
                   static_argnames=("k", "iters", "metric", "policy"))
def _kmeans_minus_minus(
    points: jnp.ndarray,
    weights: jnp.ndarray,
    valid: jnp.ndarray,
    key: jax.Array,
    *,
    k: int,
    t: float,
    iters: int,
    metric: str,
    policy: KernelPolicy,
) -> OutlierClustering:
    w = weights.astype(jnp.float32) * valid
    # greedy seeding: plain D^2 draws park centers on far outliers in about
    # a quarter of seeds, a local optimum the Lloyd loop never leaves
    seed_idx, _ = kmeanspp_seed(points, w, key, budget=k, metric=metric,
                                trials=seed_trials(k))
    centers0 = points[seed_idx]
    return _lloyd_outlier_loop(points, w, valid, centers0, k=k, t=t,
                               iters=iters, metric=metric, policy=policy)


@functools.partial(jax.jit,
                   static_argnames=("iters", "metric", "policy"))
def _kmeans_minus_minus_warm(
    points: jnp.ndarray,
    weights: jnp.ndarray,
    valid: jnp.ndarray,
    centers0: jnp.ndarray,
    *,
    t: float,
    iters: int,
    metric: str,
    policy: KernelPolicy,
) -> OutlierClustering:
    w = weights.astype(jnp.float32) * valid
    return _lloyd_outlier_loop(points, w, valid, centers0,
                               k=centers0.shape[0], t=t, iters=iters,
                               metric=metric, policy=policy)


def _lloyd_outlier_loop(points, w, valid, centers0, *, k, t, iters, metric,
                        policy) -> OutlierClustering:
    """The alternation after seeding — shared by the cold (k-means++
    seeded) and warm (previous-centers) paths; traced inline, so the cold
    path's compiled program is exactly the pre-refactor one."""

    def step(centers, _):
        # One registry-dispatched fused Lloyd step (assign + accumulate);
        # the outlier mask then corrects the accumulators with a one-hot
        # matmul over the inlier weights — no second distance pass.
        _, _, amin, dist = lloyd_step(points, w, centers, metric=metric,
                                      policy=policy)
        dist = jnp.where(valid, dist, -jnp.inf)   # padding: never an outlier
        out = _mark_outliers(dist, w, t)
        w_in = w * ~out
        sums, cnts = accumulate_by_assignment(points, w_in, amin, k)
        new_centers = jnp.where(cnts[:, None] > 0, sums / jnp.maximum(cnts, 1e-9)[:, None], centers)
        return new_centers, None

    centers, _ = jax.lax.scan(step, centers0, None, length=iters)
    dist, amin = min_argmin(points, centers, metric=metric, policy=policy)
    dist = jnp.where(valid, dist, -jnp.inf)
    out = _mark_outliers(dist, w, t)
    cost = jnp.sum(jnp.where(valid & ~out, dist, 0.0) * w)
    return OutlierClustering(
        centers=centers,
        assignment=amin.astype(jnp.int32),
        outlier=out & valid,
        cost=cost,
        distances=jnp.where(valid, dist, jnp.inf),
    )
