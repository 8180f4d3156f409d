"""Production entry point for the fused serving score op.

``score(x, c, threshold, metric=..., policy=KernelPolicy(...))``
computes, in ONE dispatch, what the serving read path previously
composed from three: distance to the nearest center (``min_argmin``),
the winning center index, and the outlier score ``dist /
max(threshold, eps)``.  Returns ``(dist (n,), idx (n,) int32,
score (n,))``.

Backends (registry: ``repro.kernels.dispatch``):

  * ``ref``     — composed oracle: ``min_argmin_ref`` + divide.  Exactly
    yesterday's semantics; the parity target for everything below.
  * ``blocked`` — chunked single pass.  Rows are tiled by ``block_n`` as
    in pdist; centers are additionally tiled by ``block_m`` with a
    running (min, argmin) carried across center tiles, so peak memory is
    ``block_n × block_m`` distances no matter how many centers.  When
    the centers fit one tile (the serving case: t ≪ n, m = k ~ tens) the
    tile loop collapses to the ref computation — bit-identical to the
    composed path.
  * ``pallas``  — one TPU kernel (``kernel.py``): double-buffered
    HBM→VMEM DMA over row tiles, centers VMEM-resident, score epilogue
    in-register.  Off a TPU only in the Pallas interpreter, when the
    policy names it (test-only, never auto-picked).
  * ``int8``    — quantized-center variant: per-center symmetric scale
    (``max|c_i| / 127``), centers stored int8, rescaled to fp32 at the
    accumulate, then the blocked single pass.  It CHANGES results
    (bounded quantization error, measured — not assumed — in
    ``benchmarks/stream_bench.py`` and gated by ``quant_max_score_err``),
    so its auto-priority is negative: callers opt in by name.

``score`` is the registry's first 2-D-tiled op: ``blocked``/``pallas``/
``int8`` register a ``block_m`` center-tile dimension, resolved through
``dispatch.resolve_tiles`` and jointly autotuned as a (block_n, block_m)
pair under the v2 cache schema.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy
from repro.kernels.pdist import ref as _ref

_DEFAULT_BLOCK_N = 16384
_TUNE_BLOCK_NS = (4096, 8192, 16384, 32768, 65536)
_DEFAULT_BLOCK_M = 128
_TUNE_BLOCK_MS = (64, 128, 256, 512)
_EPS = 1e-30  # threshold guard — matches the historical serving divide


def _finish(dist: jnp.ndarray, amin: jnp.ndarray, threshold):
    return dist, amin, dist / jnp.maximum(threshold, _EPS)


def _score_args(n: int, m: int, d: int, rng: np.random.Generator):
    """Autotuner argument factory (score takes a threshold, pdist doesn't)."""
    x = rng.standard_normal((n, d)).astype(np.float32)
    c = rng.standard_normal((m, d)).astype(np.float32)
    return (x, c, np.float32(1.0))


def _tile_min_argmin(xb: jnp.ndarray, c: jnp.ndarray, metric: str,
                     block_m: int):
    """One row block against all centers, center-tiled by ``block_m``.

    Running (min, argmin) across tiles with strict ``<`` (ties keep the
    earliest tile; ``argmin`` keeps the first minimum within a tile), so
    the result is bit-equal to the untiled ``min_argmin_ref`` argmin.
    Padded center columns are masked with +inf AFTER the distance
    computation — safe for every metric including cosine, where a padding
    *sentinel coordinate* would normalize into a legal direction.
    """
    m = c.shape[0]
    if m <= block_m:
        return _ref.min_argmin_ref(xb, c, metric)
    pad_m = (-m) % block_m
    cp = jnp.pad(c, ((0, pad_m), (0, 0)))
    n_tiles = cp.shape[0] // block_m

    def body(carry, ci):
        best_d, best_i = carry
        cc = jax.lax.dynamic_slice_in_dim(cp, ci * block_m, block_m, axis=0)
        dist = _ref.pairwise(xb, cc, metric)                  # (bn, bm)
        col = ci * block_m + jnp.arange(block_m)
        dist = jnp.where(col[None, :] < m, dist, jnp.inf)
        dmin = dist.min(axis=1)
        darg = dist.argmin(axis=1).astype(jnp.int32) + ci * block_m
        take = dmin < best_d
        return (jnp.where(take, dmin, best_d),
                jnp.where(take, darg, best_i)), None

    init = (jnp.full((xb.shape[0],), jnp.inf, xb.dtype),
            jnp.zeros((xb.shape[0],), jnp.int32))
    (bd, bi), _ = jax.lax.scan(body, init, jnp.arange(n_tiles))
    return bd, bi


def _score_rows(x, c, threshold, metric, block_n, block_m):
    """Shared blocked compute (float centers in, used by blocked + int8)."""
    n = x.shape[0]
    if n <= block_n:
        dist, amin = _tile_min_argmin(x, c, metric, block_m)
        return _finish(dist, amin, threshold)
    pad = (-n) % block_n
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xs = xp.reshape(-1, block_n, x.shape[1])
    md, ai = jax.lax.map(
        lambda xb: _tile_min_argmin(xb, c, metric, block_m), xs)
    return _finish(md.reshape(-1)[:n], ai.reshape(-1)[:n], threshold)


@dispatch.register(
    "score", "ref",
    supports=lambda metric, platform, dtype, n, m, d: metric in _ref.METRICS,
    priority=lambda platform: 0,
    default_block_n=lambda platform: _DEFAULT_BLOCK_N,
    make_args=_score_args,
)
@functools.partial(jax.jit, static_argnames=("metric", "block_n"))
def score_reference(x: jnp.ndarray, c: jnp.ndarray, threshold, *,
                    metric: str = "l2sq", block_n: int = 0):
    """Oracle: the composed three-step path as one function (tiles unused)."""
    dist, amin = _ref.min_argmin_ref(x, c, metric)
    return _finish(dist, amin, threshold)


@dispatch.register(
    "score", "blocked",
    supports=lambda metric, platform, dtype, n, m, d: metric in _ref.METRICS,
    priority=lambda platform: 1,
    default_block_n=lambda platform: _DEFAULT_BLOCK_N,
    tune_candidates=_TUNE_BLOCK_NS,
    make_args=_score_args,
    default_block_m=lambda platform: _DEFAULT_BLOCK_M,
    tune_candidates_m=_TUNE_BLOCK_MS,
)
@functools.partial(jax.jit, static_argnames=("metric", "block_n", "block_m"))
def score_blocked(x: jnp.ndarray, c: jnp.ndarray, threshold, *,
                  metric: str = "l2sq",
                  block_n: int = _DEFAULT_BLOCK_N,
                  block_m: int = _DEFAULT_BLOCK_M):
    """Chunked single pass; ≤ ``block_n × block_m`` distances live at once."""
    return _score_rows(x, c, threshold, metric, block_n, block_m)


@dispatch.register(
    "score", "pallas",
    # cosine is blocked/ref-only, matching pdist: a far-away padding
    # sentinel is a direction under a normalized metric
    supports=lambda metric, platform, dtype, n, m, d: (
        metric in _ref.PALLAS_METRICS),
    # interpret mode off-TPU is test-only: never auto-picked there
    priority=lambda platform: 10 if platform == "tpu" else -1,
    default_block_n=lambda platform: 512,
    tune_candidates=(256, 512, 1024, 2048),
    make_args=_score_args,
    default_block_m=lambda platform: 128,
    tune_candidates_m=(128, 256, 512),
)
def score_pallas_backend(x: jnp.ndarray, c: jnp.ndarray, threshold, *,
                         metric: str = "l2sq", block_n: int = 512,
                         block_m: int = 128, interpret: bool = False):
    from . import kernel as _kernel  # deferred: pallas import is optional
    return _kernel.score_pallas(x, c, threshold, metric=metric,
                                bn=block_n, bm=block_m, interpret=interpret)


@dispatch.register(
    "score", "int8",
    supports=lambda metric, platform, dtype, n, m, d: metric in _ref.METRICS,
    # changes results (quantization error): explicit opt-in only
    priority=lambda platform: -1,
    default_block_n=lambda platform: _DEFAULT_BLOCK_N,
    tune_candidates=_TUNE_BLOCK_NS,
    make_args=_score_args,
    default_block_m=lambda platform: _DEFAULT_BLOCK_M,
    tune_candidates_m=_TUNE_BLOCK_MS,
)
@functools.partial(jax.jit, static_argnames=("metric", "block_n", "block_m"))
def score_int8(x: jnp.ndarray, c: jnp.ndarray, threshold, *,
               metric: str = "l2sq",
               block_n: int = _DEFAULT_BLOCK_N,
               block_m: int = _DEFAULT_BLOCK_M):
    """Quantized-center score: per-center symmetric int8, fp32 rescale.

    ``scale_i = max|c_i| / 127`` per center row; centers round to int8
    and are rescaled to fp32 at the accumulate, then the blocked single
    pass runs unchanged.  Queries stay fp32 — only the (tiny, reusable)
    summary is quantized, the coreset-tolerance argument for bounded
    per-point distance error.  Max score error is MEASURED in
    benchmarks/stream_bench.py (``quant_max_score_err``), not assumed.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(c), axis=1) / 127.0, 1e-12)
    cq = jnp.round(c / scale[:, None]).astype(jnp.int8)
    cdq = cq.astype(jnp.float32) * scale[:, None]
    return _score_rows(x, cdq, threshold, metric, block_n, block_m)


def score(
    x: jnp.ndarray,
    c: jnp.ndarray,
    threshold,
    *,
    metric: str = "l2sq",
    policy: Optional[KernelPolicy] = None,
    block_n: Optional[int] = None,      # removed alias: raises TypeError
    use_pallas: Optional[bool] = None,  # removed alias: raises TypeError
):
    """Fused serving score: one dispatch for pdist → argmin → dist/thr.

    For each row of ``x`` (n, d): distance to the nearest row of ``c``
    (m, d), that row's index, and ``dist / max(threshold, 1e-30)``.
    Returns ``(dist (n,), idx (n,) int32, score (n,))``; ``score > 1``
    is the paper's outlier predicate.

    Backend/tile selection comes from ``policy`` (default: the process
    policy).  Resolution happens at trace time, so calls inside
    ``jax.jit`` cost nothing at runtime.
    """
    policy = dispatch.resolve_policy(policy, use_pallas=use_pallas,
                                     block_n=block_n, caller="score")
    n, d = x.shape
    reg, bn, bm = dispatch.resolve_tiles("score", policy, metric=metric,
                                         n=n, m=c.shape[0], d=d,
                                         dtype=x.dtype)
    if reg.default_block_m is None:
        return reg.impl(x, c, threshold, metric=metric, block_n=bn)
    return reg.impl(x, c, threshold, metric=metric, block_n=bn, block_m=bm)
