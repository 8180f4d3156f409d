"""Public wrapper for the fused Lloyd step (assign + weighted accumulate).

Backends (registered with ``repro.kernels.dispatch``):

  * ``pallas``  — the fused TPU kernel (l2sq/l2 only: the assignment is an
    MXU matmul and the scatter-add becomes a one-hot matmul),
  * ``blocked`` — chunked ``min_argmin`` for the assignment + a one-hot
    matmul accumulate (any metric; bounded memory),
  * ``ref``     — the pure-jnp oracle in ``ref.py``.

``backend="auto"`` picks Pallas on TPU for l2sq/l2 and blocked elsewhere;
an explicit ``pallas`` policy under the l1 metric raises (no kernel).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy
from repro.kernels.lloyd.ref import lloyd_step_ref
from repro.kernels.pdist.ops import min_argmin_blocked
from repro.kernels.pdist.ref import F32

_DEFAULT_BLOCK_N = 16384


def _lloyd_args(n: int, m: int, d: int, rng: np.random.Generator):
    x = rng.standard_normal((n, d)).astype(np.float32)
    w = rng.uniform(0.0, 2.0, size=(n,)).astype(np.float32)
    c = rng.standard_normal((m, d)).astype(np.float32)
    return (x, w, c)


def accumulate_by_assignment(x, w, amin, k: int):
    """(sums (k,d), counts (k,)) of ``w``-weighted rows grouped by ``amin``.

    One-hot matmul instead of scatter-add: MXU-friendly on TPU, vectorized
    on CPU, and backend-agnostic — the re-accumulate half of k-means--'s
    outlier-corrected step for every backend.
    """
    onehot = (amin[:, None] == jnp.arange(k, dtype=amin.dtype)[None, :])
    onehot = onehot.astype(jnp.float32) * w[:, None]           # (n, k)
    sums = jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                               precision=F32,
                               preferred_element_type=jnp.float32)
    return sums, onehot.sum(axis=0)


@dispatch.register(
    "lloyd_step", "blocked",
    # cosine: the weighted-mean center update is the spherical k-means step
    # (only the mean's direction matters — distances normalize the center)
    supports=lambda metric, platform, dtype, n, m, d: metric in ("l2sq", "l2", "l1", "cosine"),
    priority=lambda platform: 1,
    default_block_n=lambda platform: _DEFAULT_BLOCK_N,
    tune_candidates=(4096, 8192, 16384, 32768, 65536),
    make_args=_lloyd_args,
)
@functools.partial(jax.jit, static_argnames=("metric", "block_n"))
def lloyd_step_blocked(x, w, c, *, metric: str = "l2sq",
                       block_n: int = _DEFAULT_BLOCK_N):
    """Chunked assignment + one-hot matmul accumulate (bounded memory)."""
    dist, amin = min_argmin_blocked(x, c, metric=metric, block_n=block_n)
    sums, counts = accumulate_by_assignment(x, w, amin, c.shape[0])
    return sums, counts, amin, dist


@dispatch.register(
    "lloyd_step", "ref",
    supports=lambda metric, platform, dtype, n, m, d: metric in ("l2sq", "l2", "l1", "cosine"),
    priority=lambda platform: 0,
    default_block_n=lambda platform: _DEFAULT_BLOCK_N,
    make_args=_lloyd_args,
)
@functools.partial(jax.jit, static_argnames=("metric", "block_n"))
def lloyd_step_reference(x, w, c, *, metric: str = "l2sq", block_n: int = 0):
    return lloyd_step_ref(x, w, c, metric)


@dispatch.register(
    "lloyd_step", "pallas",
    supports=lambda metric, platform, dtype, n, m, d: metric in ("l2sq", "l2"),
    priority=lambda platform: 10 if platform == "tpu" else -1,
    default_block_n=lambda platform: 1024,
    tune_candidates=(512, 1024, 2048),
    make_args=_lloyd_args,
)
def lloyd_step_pallas_backend(x, w, c, *, metric: str = "l2sq",
                              block_n: int = 1024, interpret: bool = False):
    from repro.kernels.lloyd.kernel import lloyd_step_pallas
    return lloyd_step_pallas(x, w, c, metric=metric, bn=block_n,
                             interpret=interpret)


def lloyd_step(
    x,
    w,
    c,
    *,
    metric: str = "l2sq",
    policy: Optional[KernelPolicy] = None,
    use_pallas: Optional[bool] = None,  # removed alias: raises TypeError
):
    """Returns (sums (k,d), counts (k,), assignment (n,), dist (n,))."""
    policy = dispatch.resolve_policy(policy, use_pallas=use_pallas,
                                     caller="lloyd_step")
    n, d = x.shape
    reg, bn = dispatch.resolve("lloyd_step", policy, metric=metric,
                               n=n, m=c.shape[0], d=d, dtype=x.dtype)
    return reg.impl(x, w, c, metric=metric, block_n=bn)
