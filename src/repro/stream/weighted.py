"""Weighted Summary-Outliers: Algorithm 1 generalized to weighted inputs.

A record (x, w) stands for w coincident unit points.  Two changes from the
unit-weight algorithm in ``repro.core.summary``:

* Line 6 samples the m round-samples with probability proportional to
  weight (a record of weight w is w times as likely as a unit record);
* Line 8 grows the ball to the smallest radius rho_i whose captured
  *weight mass* reaches beta * W_i (W_i = total remaining weight), and the
  stopping rule |X_i| <= 8t becomes W_i <= 8t.

With unit weights both rules reduce exactly to the paper's.  The progress
guarantee is unchanged and deterministic: every round removes at least a
beta fraction of the remaining *mass*, so the loop runs at most
ceil(log(W/8t) / -log(1-beta)) rounds regardless of how the mass is
distributed over records.

Why this makes a summary-of-summaries well defined: a weighted summary Q of
X conserves mass (sum of Q's weights == total weight of X) and each output
record is an input point carrying the mass of the inputs mapped to it.
Summarizing the concatenation of two summaries Q1 u Q2 therefore produces a
summary of X1 u X2 whose information loss telescopes — each level of
re-summarization adds at most one Algorithm-1 loss term on top of the loss
already incurred below (triangle inequality through the intermediate
representative).  That is the merge-and-reduce composition the stream tree
(``repro.stream.tree``) relies on.

Host-driven like ``summary_outliers_compact``: set logic in numpy, the
distance inner loop stays jitted (``min_argmin``, backend-selected via
``KernelPolicy``).  Stream leaves and merges are small (10^3..10^4 records),
so the host loop is never the bottleneck; the latency-critical query path
in ``repro.stream.service`` is fully jitted.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.dispatch import KernelPolicy, resolve_policy
from repro.kernels.pdist.ops import min_argmin

_FAR = 1e30  # sentinel coordinate for rows padded into a jit bucket


def _bucket(n: int, lo: int = 256) -> int:
    """Next power-of-two >= n (min lo): bounds the number of jit shapes.
    Shared by the summarize and scoring paths (repro.stream.service)."""
    b = lo
    while b < n:
        b <<= 1
    return b


def categorical_by_weight(key: jax.Array, w: np.ndarray, shape) -> np.ndarray:
    """Sample ids (with replacement) with probability ∝ ``w`` (all > 0).

    Logits are -inf-padded to the shared power-of-two bucket so the jitted
    categorical compiles once per bucket, not once per distinct row count —
    the same idiom as the distance calls.  Shared by every host-driven
    summarizer (weighted Algorithm 1, ball_cover, coreset seeding).
    """
    logits = np.full((_bucket(w.size),), -np.inf, np.float32)
    logits[:w.size] = np.log(w)
    obs.counter("summary.h2d_bytes").inc(logits.nbytes)
    return np.asarray(jax.random.categorical(key, jnp.asarray(logits),
                                             shape=shape))


def _min_argmin_bucketed(xr: np.ndarray, c: np.ndarray, *, metric: str,
                         policy: Optional[KernelPolicy]):
    """min_argmin with the row count padded to a power-of-two bucket, so the
    jitted kernel compiles once per bucket instead of once per round (the
    remaining set shrinks every round and would otherwise retrace).  The
    padded rows and the centers sent to the device are counted in
    ``summary.h2d_bytes``."""
    nr = xr.shape[0]
    nb = _bucket(nr)
    if nb > nr:
        xr = np.concatenate(
            [xr, np.full((nb - nr, xr.shape[1]), _FAR, np.float32)])
    obs.counter("summary.h2d_bytes").inc(xr.nbytes + np.asarray(c).nbytes)
    mind, amin = min_argmin(xr, c, metric=metric, policy=policy)
    return np.asarray(mind)[:nr], np.asarray(amin)[:nr]


class WeightedSummary(NamedTuple):
    """Compact (no padding) weighted summary of a weighted point set.

    points       (s, d) f32  — summary points (subset of the input rows)
    weights      (s,) f32    — mass mapped to each point; conserves input mass
    is_candidate (s,) bool   — True for survivors X_r (outlier candidates)
    n_rounds     int         — rounds the ball-growing loop ran
    total_weight float       — input mass (== weights.sum() up to fp error)
    indices      (s,) i64 | None — row ids of the summary points in the
                 summarizer's *input* (after zero-weight rows are dropped the
                 ids still refer to the original input rows).  None once the
                 provenance is lost (merges, checkpoint restores).
    """

    points: np.ndarray
    weights: np.ndarray
    is_candidate: np.ndarray
    n_rounds: int
    total_weight: float
    indices: Optional[np.ndarray] = None


def max_rounds(total_weight: float, t: int, beta: float) -> int:
    """Deterministic round bound: each round captures >= beta of the mass."""
    stop = max(8 * t, 1)
    if total_weight <= stop:
        return 0
    return max(1, int(math.ceil(math.log(total_weight / stop)
                                / -math.log1p(-beta))))


def weighted_summary_outliers(
    points,
    weights,
    key: jax.Array,
    *,
    k: int,
    t: int,
    alpha: float = 2.0,
    beta: float = 0.45,
    metric: str = "l2sq",
    policy: Optional[KernelPolicy] = None,
    block_n: Optional[int] = None,      # removed alias: raises TypeError
    use_pallas: Optional[bool] = None,  # removed alias: raises TypeError
) -> WeightedSummary:
    """Weighted Summary-Outliers over records (points[i], weights[i])."""
    from repro.summarize.base import clean_weighted_input, empty_summary

    policy = resolve_policy(policy, use_pallas=use_pallas, block_n=block_n,
                            caller="weighted_summary_outliers")
    x, w, orig_ids, total = clean_weighted_input(points, weights)
    n = x.shape[0]
    if n == 0:
        return empty_summary(x.shape[1])

    kappa = max(k, max(1, math.ceil(math.log(max(n, 2)))))
    m = max(1, int(math.ceil(alpha * kappa)))
    stop = max(8 * t, 1)
    bound = max_rounds(total, t, beta) + 4  # +4: fp slack on the mass sums

    remaining = np.arange(n, dtype=np.int64)
    acc_w = np.zeros(n, np.float32)          # mass captured per center
    center_ids: list[np.ndarray] = []
    rounds = 0
    while remaining.size and float(w[remaining].sum()) > stop and rounds < bound:
        key, sk = jax.random.split(key)
        wr = w[remaining]
        # Line 6 (weighted): sample m records with replacement, p ∝ weight.
        pick = categorical_by_weight(sk, wr, (m,))
        idx = remaining[pick]                 # global ids of this round's S_i
        mind, amin = _min_argmin_bucketed(x[remaining], x[idx], metric=metric,
                                          policy=policy)
        # Line 8 (weighted): smallest rho capturing >= beta * W_i of mass.
        order = np.argsort(mind, kind="stable")
        cumw = np.cumsum(wr[order])
        kpos = int(np.searchsorted(cumw, beta * float(wr.sum())))
        kpos = min(kpos, order.size - 1)
        rho = mind[order[kpos]]
        captured = mind <= rho                # samples sit at rho=0: always in
        # Line 9: each captured record's full mass goes to its nearest sample.
        np.add.at(acc_w, idx[amin[captured]], wr[captured])
        center_ids.append(np.unique(idx))
        remaining = remaining[~captured]
        rounds += 1
    obs.counter("summary.rounds").inc(rounds)

    centers = (np.unique(np.concatenate(center_ids)) if center_ids
               else np.empty(0, np.int64))
    # coincident sampled points can tie on argmin so one of them captures
    # all the mass; drop the zero-mass twins to keep the weights>0 invariant
    centers = centers[acc_w[centers] > 0]
    pts = np.concatenate([x[centers], x[remaining]])
    wts = np.concatenate([acc_w[centers], w[remaining]])
    cand = np.concatenate([np.zeros(centers.size, bool),
                           np.ones(remaining.size, bool)])
    return WeightedSummary(points=pts.astype(np.float32),
                           weights=wts.astype(np.float32),
                           is_candidate=cand,
                           n_rounds=rounds,
                           total_weight=total,
                           indices=orig_ids[np.concatenate([centers, remaining])])


def merge_summaries(summaries: Sequence[WeightedSummary]) -> WeightedSummary:
    """Concatenate weighted summaries (the 'merge' half of merge-and-reduce).

    Pure union — no information is lost; mass is conserved exactly.
    """
    live = [s for s in summaries if s.points.shape[0]]
    if not live:
        return WeightedSummary(np.zeros((0, 0), np.float32),
                               np.zeros((0,), np.float32),
                               np.zeros((0,), bool), 0, 0.0)
    return WeightedSummary(
        points=np.concatenate([s.points for s in live]),
        weights=np.concatenate([s.weights for s in live]),
        is_candidate=np.concatenate([s.is_candidate for s in live]),
        n_rounds=max(s.n_rounds for s in live),
        total_weight=float(sum(s.total_weight for s in live)),
    )


def resummarize(
    summaries: Sequence[WeightedSummary],
    key: jax.Array,
    *,
    k: int,
    t: int,
    alpha: float = 2.0,
    beta: float = 0.45,
    metric: str = "l2sq",
    policy: Optional[KernelPolicy] = None,
) -> WeightedSummary:
    """The 'reduce' half: weighted Summary-Outliers on the merged union.

    Keeps the full outlier budget t at every level so that up to t true
    outliers survive as candidates through any number of merges.
    """
    merged = merge_summaries(summaries)
    if merged.points.shape[0] == 0:
        return merged
    return weighted_summary_outliers(
        merged.points, merged.weights, key, k=k, t=t, alpha=alpha, beta=beta,
        metric=metric, policy=policy)
