"""The plain reference that decides ``correct``, and its control.

Everything here is numpy in float64 on the host (the control alone runs
through ``jax.numpy``, on whatever device the caller has), imports nothing
of the program, and is handed only what the timed path produced: the
served answers, the installed models and the tree roots they were fit on.

Numbers compared (each against its limit from the configuration file):

* ``dist_ulps`` / ``score_ulps`` (read path): the largest error of a
  served distance, or of a served score times the threshold, against the
  float64 distance to the nearest center, in float32 ulps of
  ``|x|^2 + |c|^2`` -- the scale the chip's ``x2 + c2 - 2 x.c`` rounds at.
* ``argmin_bad`` / ``flag_bad`` (read path): served nearest centers and
  outlier flags that differ from the reference's, away from near-ties
  (second-nearest within ``dist_ulps``'s limit) and from the threshold.
* ``missing``: rows due in the window that never got an answer.
* ``thr_ulps`` (refresh): the installed model's threshold against the
  reference one -- the largest inlier distance after marking, farthest
  first, the records whose cumulative weight stays within t, from the
  model's centers on the root it was fit on -- in ulps of the boundary
  record's ``|x|^2 + |c|^2``.
* ``center_ulps`` (refresh): how far one more float64 weighted k-means--
  step would move the installed centers on the root they were fit on --
  assign every record to its nearest center, mark the farthest mass
  within t, take each center's inlier mean -- as the largest move of a
  center in float32 ulps of the root-mean-square norm of its inliers (the
  size its float32 sums round at).  A fit that stopped short of a
  k-means-- fixed point, kept its seeding or moved a center off its
  inliers' mean reads high.
* ``trained_gap`` (refresh, and the exchange between chips): the model's
  trained mass against the mass of every site's root.
* ``mass_gap`` / ``window_short`` (write path): each tree's root mass
  against the raw points its live summaries span (summaries conserve mass),
  and how far that span falls short of the window.
* ``fed_gap`` (write path): points the trees took in against points the
  benchmark fed.

Control: the reference put in the program's place, computed at the next
precision below the configuration's (float32 matmuls at ``highest``):
``Precision.HIGH``, three bfloat16 passes: served distances and scores
from it (read path); the threshold from its distances and its marking,
and one more k-means-- step taken with its assignment and marking, in the
place of the installed model's (refresh).  The CPU computes every float32
matmul in full, so the CPU tests write the three passes out instead.
"""
from __future__ import annotations

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)


# ------------------------------------------------------------ distances
def nearest(x, c, block: int = 16384):
    """float64 squared distance to the nearest and second-nearest center,
    and the nearest index (first on ties)."""
    c64 = np.asarray(c, np.float64)
    c2 = np.square(c64).sum(1)
    best = np.empty(x.shape[0])
    second = np.full(x.shape[0], np.inf)
    arg = np.empty(x.shape[0], np.int64)
    for i in range(0, x.shape[0], block):
        xb = np.asarray(x[i:i + block], np.float64)
        d = np.maximum(np.square(xb).sum(1)[:, None] + c2[None, :]
                       - 2.0 * xb @ c64.T, 0.0)
        a = d.argmin(1)
        rows = np.arange(d.shape[0])
        arg[i:i + block] = a
        best[i:i + block] = d[rows, a]
        if c64.shape[0] > 1:
            d[rows, a] = np.inf
            second[i:i + block] = d.min(1)
    return best, arg, second


def scale_of(x, c, arg):
    """|x|^2 + |c_arg|^2 per row: the size a float32 distance rounds at."""
    return (np.square(np.asarray(x, np.float64)).sum(1)
            + np.square(np.asarray(c, np.float64)).sum(1)[arg])


def control_nearest(x, c, emulate: bool = False):
    """Nearest-center distance with the matmul at ``Precision.HIGH``
    (three bfloat16 passes), as a program tempted to drop ``highest``
    would compute it.  ``emulate`` writes the three passes out
    (``hi*hi + hi*lo + lo*hi``), for backends that compute every float32
    matmul in full (the CPU).  Returns (distance float32, index)."""
    import jax
    import jax.numpy as jnp
    prec = jax.lax.Precision

    def split(a):
        hi = a.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)

    @jax.jit
    def run(xb, cb):
        if emulate:
            xh, xl = split(xb)
            ch, cl = split(cb)
            dot = sum(jnp.matmul(a, b.T, precision=prec.HIGHEST)
                      for a, b in ((xh, ch), (xh, cl), (xl, ch)))
        else:
            dot = jnp.matmul(xb, cb.T, precision=prec.HIGH)
        d = (jnp.sum(xb * xb, 1)[:, None] + jnp.sum(cb * cb, 1)[None, :]
             - 2.0 * dot)
        d = jnp.maximum(d, 0.0)
        return d.min(1), d.argmin(1)

    cj = jnp.asarray(c, jnp.float32)
    dist, arg = [], []
    for i in range(0, x.shape[0], 65536):
        dm, am = run(jnp.asarray(x[i:i + 65536], jnp.float32), cj)
        dist.append(np.asarray(dm))
        arg.append(np.asarray(am))
    return np.concatenate(dist), np.concatenate(arg)


# ------------------------------------------------------------ read path
def score_numbers(x, served, centers, threshold, tie_ulps: float) -> dict:
    """``served``: dict of arrays ``center``, ``distance``, ``score``,
    ``flag`` (NaN distance = no answer) for the rows ``x``."""
    d_ref, a_ref, second = nearest(x, centers)
    scale = scale_of(x, centers, a_ref)
    tol = tie_ulps * EPS32 * scale
    thr = float(threshold)
    ok = ~np.isnan(served["distance"])
    err = np.abs(served["distance"][ok] - d_ref[ok]) / (EPS32 * scale[ok])
    s_err = (np.abs(served["score"][ok] * thr - d_ref[ok])
             / (EPS32 * scale[ok]))
    tie = (second - d_ref) <= tol
    bad_arg = ok & (served["center"] != a_ref) & ~tie
    boundary = np.abs(d_ref - thr) <= tol
    bad_flag = ok & (served["flag"] != (d_ref > thr)) & ~boundary
    return {"dist_ulps": float(err.max()) if err.size else 0.0,
            "score_ulps": float(s_err.max()) if s_err.size else 0.0,
            "argmin_bad": int(bad_arg.sum()),
            "flag_bad": int(bad_flag.sum()),
            "missing": int((~ok).sum())}


def control_served(x, centers, threshold, emulate: bool = False) -> dict:
    """What the read path would serve with its matmul at HIGH."""
    dist, arg = control_nearest(x, centers, emulate)
    dist = dist.astype(np.float64)
    thr = max(float(threshold), 1e-30)
    return {"center": arg, "distance": dist, "score": dist / thr,
            "flag": dist / thr > 1.0}


# ------------------------------------------------------------ refresh
def mark_outliers(dist, w, t: float):
    """Farthest first, while the cumulative weight stays within t."""
    order = np.argsort(-dist, kind="stable")
    cum = np.cumsum(np.asarray(w, np.float64)[order])
    out = np.zeros(dist.size, bool)
    out[order] = (cum <= t) & (np.asarray(w)[order] > 0)
    return out


def lloyd_step(pts, w, centers, t: float, dist=None, arg=None):
    """One weighted k-means-- step from ``centers``: nearest center (from
    ``dist``/``arg`` where given, else in float64), the farthest mass
    within t marked, each center moved to its inliers' weighted mean (a
    center with no inlier stays).  Returns the new centers (float64) and
    the root-mean-square norm of each center's inliers."""
    if dist is None:
        dist, arg, _ = nearest(pts, centers)
    k = centers.shape[0]
    w_in = np.where(mark_outliers(dist, w, t), 0.0,
                    np.asarray(w, np.float64))
    mass = np.bincount(arg, weights=w_in, minlength=k)
    x64 = np.asarray(pts, np.float64)
    sums = np.stack([np.bincount(arg, weights=w_in * x64[:, j], minlength=k)
                     for j in range(x64.shape[1])], 1)
    sq = np.bincount(arg, weights=w_in * np.square(x64).sum(1), minlength=k)
    has = mass > 0
    new = np.asarray(centers, np.float64).copy()
    new[has] = sums[has] / mass[has, None]
    rms = np.sqrt(np.where(has, sq / np.where(has, mass, 1.0), 0.0))
    return new, rms


def center_shift_ulps(pts, w, centers, t: float, d_ref=None,
                      a_ref=None) -> float:
    """The largest move of a center under one float64 k-means-- step, in
    float32 ulps of the root-mean-square norm of its inliers."""
    new, rms = lloyd_step(pts, w, centers, t, d_ref, a_ref)
    move = np.sqrt(np.square(new - np.asarray(centers, np.float64)).sum(1))
    scale = EPS32 * np.maximum(rms, np.finfo(np.float32).tiny)
    return float((move / scale).max())


def refresh_numbers(pts, w, model: dict, t: float,
                    dist=None, arg=None) -> dict:
    """The installed ``model`` (centers, threshold, trained_weight) against
    the reference on the root records ``pts`` with weights ``w``.
    ``dist``/``arg`` stand in for the program's own distances when a
    control computes the model's threshold and last step itself."""
    centers = model["centers"]
    d_ref, a_ref, _ = nearest(pts, centers)
    inl = ~mark_outliers(d_ref, w, t)
    b = int(np.flatnonzero(inl)[np.argmax(d_ref[inl])])
    thr_ref = d_ref[b]
    scale = float(scale_of(pts[b:b + 1], centers, a_ref[b:b + 1])[0])
    thr = float(model["threshold"])
    if dist is None:
        shift = center_shift_ulps(pts, w, centers, t, d_ref, a_ref)
    else:        # control: its own distances, marking and last step
        c_inl = ~mark_outliers(dist, w, t)
        thr = float(dist[c_inl].max())
        moved, _ = lloyd_step(pts, w, centers, t, dist, arg)
        shift = center_shift_ulps(pts, w, moved, t)
    return {"thr_ulps": abs(thr - thr_ref) / (EPS32 * scale),
            "center_ulps": shift,
            "trained_gap": abs(float(model["trained_weight"])
                               - float(np.sum(w, dtype=np.float64)))}


# ------------------------------------------------------------ write path
def tree_numbers(trees: list[dict]) -> dict:
    """``trees``: per tree ``weights`` (root), ``spans`` [(min_seq,
    max_seq)] of its live summaries, ``total`` points ingested and
    ``window`` (its share of the window, or None)."""
    mass_gap = window_short = 0.0
    for tr in trees:
        lo = min((s for s, _ in tr["spans"]), default=tr["total"])
        mass = float(np.sum(tr["weights"], dtype=np.float64))
        mass_gap = max(mass_gap, abs(mass - (tr["total"] - lo)))
        covered = sorted(tr["spans"])
        holes = sum(max(0, b[0] - a[1]) + max(0, a[1] - b[0])
                    for a, b in zip(covered, covered[1:]))
        mass_gap = max(mass_gap, float(holes))
        if tr["window"] is not None:
            want = min(tr["window"], tr["total"])
            window_short = max(window_short, want - (tr["total"] - lo))
    return {"mass_gap": mass_gap, "window_short": float(window_short)}
