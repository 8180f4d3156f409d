"""Weighted k-means++ (Arthur & Vassilvitskii 2007) D^p seeding.

Used in three roles:
  * seeding for the second-level k-means-- at the coordinator,
  * the paper's `k-means++` *baseline summary*: run seeding with a budget of
    B = O(k log n + t) centers on the local data, weight each center by the
    number of points nearest to it,
  * seeding inside k-means|| post-processing.

p = 2 for (k,t)-means, p = 1 for (k,t)-median.

Each step draws ``trials`` candidates by D^p and keeps the one that lowers
the weighted potential most: ``trials=1`` is plain seeding, ``trials > 1``
greedy k-means++ (Arthur & Vassilvitskii 2007, §6; the scikit-learn
default).  Under outliers this matters: a far outlier is the likeliest
single D^p draw once most clusters are covered, but it lowers the
potential by its own weight only, while a point of an uncovered cluster
lowers it by that cluster's whole mass.  The coordinator's k-means-- seeds this way; the k-means++ baseline
summary keeps the paper's plain seeding (``trials=1``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.summary import Summary
from repro.kernels.dispatch import KernelPolicy, resolve_policy
from repro.kernels.pdist.ops import min_argmin
from repro.kernels.pdist.ref import F32


def _dist_to(x, c, metric):
    if metric == "l1":
        return jnp.abs(x - c[None, :]).sum(-1)
    if metric == "cosine":
        xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-30)
        cn = c / jnp.maximum(jnp.linalg.norm(c), 1e-30)
        return jnp.clip(1.0 - jnp.matmul(xn, cn, precision=F32), 0.0, 2.0)
    sq = ((x - c[None, :]) ** 2).sum(-1)
    return sq if metric == "l2sq" else jnp.sqrt(sq)


@functools.partial(jax.jit, static_argnames=("budget", "metric", "trials"))
def kmeanspp_seed(
    x: jnp.ndarray,
    w: jnp.ndarray,
    key: jax.Array,
    *,
    budget: int,
    metric: str = "l2sq",
    trials: int = 1,
):
    """Pick ``budget`` rows of ``x`` by weighted D^p sampling, the best of
    ``trials`` candidates per step.

    Returns (center indices (budget,) int32, min-dist of every point to the
    chosen set).  Zero-weight rows are never chosen.
    """
    n = x.shape[0]
    w = w.astype(jnp.float32)

    def body(carry, _):
        key, mind, chosen_any = carry
        key, sk = jax.random.split(key)
        score = w * mind
        # first pick: plain weighted sampling (mind starts at +inf -> use w)
        score = jnp.where(jnp.isinf(mind), w, score)
        score = jnp.where(score.sum() > 0, score, w)
        logits = jnp.log(jnp.maximum(score, 1e-30))
        logits = jnp.where(w > 0, logits, -jnp.inf)
        cand = jax.random.categorical(sk, logits, shape=(trials,))
        dc = jax.vmap(lambda i: _dist_to(x, x[i], metric))(cand)
        pot = jnp.sum(w * jnp.minimum(mind, dc), axis=1)   # (trials,)
        best = jnp.argmin(pot)
        idx, d = cand[best].astype(jnp.int32), dc[best]
        mind = jnp.minimum(mind, d)
        return (key, mind, chosen_any | True), idx

    # x-derived init keeps the scan carry's shard_map vma tag consistent.
    mind0 = jnp.full((n,), jnp.inf, jnp.float32) + x[:, 0] * 0
    init = (key, mind0, False)
    (_, mind, _), idx = jax.lax.scan(body, init, None, length=budget)
    return idx, mind


def pp_budget(n: int, k: int, t: int) -> int:
    """The paper's baseline budget O(k log n + t)."""
    return int(k * max(1, math.ceil(math.log(max(n, 2)))) + t)


def kmeanspp_summary(
    x: jnp.ndarray,
    key: jax.Array,
    *,
    budget: int,
    metric: str = "l2sq",
    policy: Optional[KernelPolicy] = None,
) -> Summary:
    """The `k-means++` baseline summary: budgeted seeding + nearest counts."""
    # resolve the process default eagerly: a jitted policy=None would freeze
    # whatever default the first trace saw into the compile cache
    policy = resolve_policy(policy)
    return _kmeanspp_summary(x, key, budget=budget, metric=metric,
                             policy=policy)


@functools.partial(jax.jit, static_argnames=("budget", "metric", "policy"))
def _kmeanspp_summary(
    x: jnp.ndarray,
    key: jax.Array,
    *,
    budget: int,
    metric: str,
    policy: KernelPolicy,
) -> Summary:
    n, d = x.shape
    w1 = jnp.ones((n,), jnp.float32)
    idx, _ = kmeanspp_seed(x, w1, key, budget=budget, metric=metric)
    centers = x[idx]
    _, amin = min_argmin(x, centers, metric=metric, policy=policy)
    counts = jnp.zeros((budget,), jnp.float32).at[amin].add(1.0)
    sigma = idx[amin]
    return Summary(
        indices=idx,
        points=centers,
        weights=counts,
        is_candidate=jnp.zeros((budget,), bool),
        valid=jnp.ones((budget,), bool),
        sigma=sigma,
        n_rounds=jnp.int32(budget),
        n_remaining=jnp.int32(0),
    )
