"""The ``ball_cover`` summarizer: heavy-noise-robust ball-cover aggregation.

In the heavy-noise regime (t >> k, e.g. 10% scattered noise) Algorithm 1
has a known weakness: round samples are drawn uniformly from the remainder,
so noise points get sampled in proportion to their mass and *every sampled
point becomes a center* — the summary fills up with singleton noise balls.
Guo & Li (arXiv:1810.07852) fix this for distributed k-center/means with
outliers by aggregating the cover: only balls that capture a non-trivial
mass survive as centers.

This implementation keeps Algorithm 1's round structure (sample m records
∝ weight, grow the shared radius rho to the smallest value capturing a
beta fraction of the remaining mass — so the deterministic
ceil(log(W/8t)/-log(1-beta)) round bound is untouched) and adds the
aggregation step:

  * a sampled ball is **heavy** when it individually captures at least
    ``min_ball_frac * beta * W_i / m`` mass (its fair share of the round's
    capture, scaled down by ``min_ball_frac``);
  * captured records whose nearest sample is *light* are re-routed to
    their nearest **heavy** sample (one more tiny min_argmin over <= m
    centers), and only heavy samples survive as summary centers.

The captured set per round is identical to Algorithm 1's, so progress and
the round bound are unchanged; only center provenance differs.  Survivors
of the final round are outlier candidates (mass <= 8t), exactly like the
paper summarizer, so the second level still sees the true outliers.
"""
from __future__ import annotations

import math

import numpy as np
import jax

from repro import obs
from repro.summarize.base import (clean_weighted_input, empty_summary,
                                  register_summarizer)


def _summarize(points, weights, key, *, k, t, alpha, beta, metric,
               kernel_policy, min_ball_frac: float = 0.5):
    from repro.stream.weighted import (_min_argmin_bucketed, WeightedSummary,
                                       categorical_by_weight, max_rounds)

    x, w, orig, total = clean_weighted_input(points, weights)
    n = x.shape[0]
    if n == 0:
        return empty_summary(np.asarray(points, np.float32).shape[-1])

    kappa = max(k, max(1, math.ceil(math.log(max(n, 2)))))
    m = max(1, int(math.ceil(alpha * kappa)))
    stop = max(8 * t, 1)
    bound = max_rounds(total, t, beta) + 4  # +4: fp slack on the mass sums

    remaining = np.arange(n, dtype=np.int64)
    acc_w = np.zeros(n, np.float32)
    center_ids: list[np.ndarray] = []
    rounds = 0
    while remaining.size and float(w[remaining].sum()) > stop and rounds < bound:
        key, sk = jax.random.split(key)
        wr = w[remaining]
        pick = categorical_by_weight(sk, wr, (m,))
        idx = remaining[pick]                 # global ids of this round's S_i
        mind, amin = _min_argmin_bucketed(x[remaining], x[idx], metric=metric,
                                          policy=kernel_policy)
        order = np.argsort(mind, kind="stable")
        cumw = np.cumsum(wr[order])
        kpos = int(np.searchsorted(cumw, beta * float(wr.sum())))
        kpos = min(kpos, order.size - 1)
        rho = mind[order[kpos]]
        captured = mind <= rho                # identical to Algorithm 1

        # --- aggregation: fold light balls into heavy ones ---
        ball_mass = np.zeros((m,), np.float32)
        np.add.at(ball_mass, amin[captured], wr[captured])
        heavy = ball_mass >= min_ball_frac * beta * float(wr.sum()) / m
        if heavy.any() and not heavy.all():
            light_pt = captured & ~heavy[amin]
            if light_pt.any():
                _, re_amin = _min_argmin_bucketed(
                    x[remaining[light_pt]], x[idx[heavy]], metric=metric,
                    policy=kernel_policy)
                np.add.at(acc_w, idx[heavy][re_amin], wr[light_pt])
            kept = captured & heavy[amin]
            np.add.at(acc_w, idx[amin[kept]], wr[kept])
            center_ids.append(np.unique(idx[heavy]))
        else:
            # no ball stands out (or all do): plain Algorithm 1 assignment
            np.add.at(acc_w, idx[amin[captured]], wr[captured])
            center_ids.append(np.unique(idx))
        remaining = remaining[~captured]
        rounds += 1
    obs.counter("summary.rounds").inc(rounds)

    centers = (np.unique(np.concatenate(center_ids)) if center_ids
               else np.empty(0, np.int64))
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
                           indices=orig[np.concatenate([centers, remaining])])


def _record_bound(params, *, k, t, alpha, beta, max_points, leaf_size):
    # never more centers than the paper summarizer (a subset of its samples)
    from repro.summarize.paper import _record_bound as paper_bound

    return paper_bound({}, k=k, t=t, alpha=alpha, beta=beta,
                       max_points=max_points, leaf_size=leaf_size)


register_summarizer(
    "ball_cover",
    summarize=_summarize,
    supports=lambda metric, k, t: True,
    priority=5,    # auto falls back here only if paper ever opts out
    record_bound=_record_bound,
    description="Guo & Li-style ball-cover aggregation: light balls fold "
                "into heavy ones, robust to heavy (t >> k) noise",
)
