"""Production entry point for fused min-distance + argmin.

``min_argmin(x, c, metric=..., policy=KernelPolicy(...))``

Dispatches through the backend registry (``repro.kernels.dispatch``):

  * ``pallas``  — the TPU kernel (``kernel.py``); off a TPU only in the
    Pallas interpreter, and only when the policy names it
    (``KernelPolicy(backend="pallas", interpret=True)``),
  * ``blocked`` — a chunked pure-jnp path that never materializes more than
    ``block_n × m`` distances at once (the (n, m) matrix for the paper's
    datasets would be ~GBs; chunking keeps the working set cache-sized on
    CPU and VMEM-sized on TPU),
  * ``ref``     — the oracle in ``ref.py`` (full (n, m) matrix).

``backend="auto"`` picks Pallas on TPU and blocked elsewhere; ``block_n``
comes from the policy, the autotuner's measured tile, or the backend
default.  All paths agree with ``ref.min_argmin_ref`` (tested in
tests/test_kernels.py and tests/test_dispatch.py, incl. interpret=True
kernel sweeps).

The pre-registry ``use_pallas=``/``block_n=`` keyword aliases are removed;
passing either raises a ``TypeError`` naming the ``KernelPolicy``
replacement.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy
from . import ref as _ref

_DEFAULT_BLOCK_N = 16384
_TUNE_BLOCK_NS = (4096, 8192, 16384, 32768, 65536)


def _block_min_argmin(xb: jnp.ndarray, c: jnp.ndarray, metric: str):
    """One n-block against all centers. For l1, chunk centers to bound the
    (bn, mc, d) broadcast."""
    if metric == "l1":
        m = c.shape[0]
        mc = min(m, 64)
        pad_m = (-m) % mc
        cp = jnp.pad(c, ((0, pad_m), (0, 0)), constant_values=jnp.inf)
        n_chunks = cp.shape[0] // mc

        def body(carry, ci):
            best_d, best_i = carry
            cc = jax.lax.dynamic_slice_in_dim(cp, ci * mc, mc, axis=0)
            d = jnp.abs(xb[:, None, :] - cc[None, :, :]).sum(-1)  # (bn, mc)
            dmin = d.min(axis=1)
            darg = d.argmin(axis=1).astype(jnp.int32) + ci * mc
            take = dmin < best_d
            return (jnp.where(take, dmin, best_d), jnp.where(take, darg, best_i)), None

        init = (jnp.full((xb.shape[0],), jnp.inf, xb.dtype),
                jnp.zeros((xb.shape[0],), jnp.int32))
        (bd, bi), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
        return bd, bi
    return _ref.min_argmin_ref(xb, c, metric)


@dispatch.register(
    "min_argmin", "blocked",
    supports=lambda metric, platform, dtype, n, m, d: metric in _ref.METRICS,
    priority=lambda platform: 1,
    default_block_n=lambda platform: _DEFAULT_BLOCK_N,
    tune_candidates=_TUNE_BLOCK_NS,
)
@functools.partial(jax.jit, static_argnames=("metric", "block_n"))
def min_argmin_blocked(x: jnp.ndarray, c: jnp.ndarray, *,
                       metric: str = "l2sq",
                       block_n: int = _DEFAULT_BLOCK_N):
    """Chunked jnp path: at most ``block_n × m`` distances live at once."""
    n = x.shape[0]
    if n <= block_n:
        return _block_min_argmin(x, c, metric)
    pad = (-n) % block_n
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xs = xp.reshape(-1, block_n, x.shape[1])
    md, ai = jax.lax.map(lambda xb: _block_min_argmin(xb, c, metric), xs)
    return md.reshape(-1)[:n], ai.reshape(-1)[:n]


@dispatch.register(
    "min_argmin", "ref",
    supports=lambda metric, platform, dtype, n, m, d: metric in _ref.METRICS,
    priority=lambda platform: 0,
    default_block_n=lambda platform: _DEFAULT_BLOCK_N,
)
@functools.partial(jax.jit, static_argnames=("metric", "block_n"))
def min_argmin_reference(x: jnp.ndarray, c: jnp.ndarray, *,
                         metric: str = "l2sq", block_n: int = 0):
    """Oracle backend; materializes the full (n, m) matrix (block_n unused)."""
    return _ref.min_argmin_ref(x, c, metric)


@dispatch.register(
    "min_argmin", "pallas",
    # cosine is blocked/ref-only: the kernel's far-away padding sentinel is
    # a direction under a normalized metric, not "infinitely far"
    supports=lambda metric, platform, dtype, n, m, d: metric in _ref.PALLAS_METRICS,
    # interpret mode off-TPU is test-only: never auto-picked there
    priority=lambda platform: 10 if platform == "tpu" else -1,
    default_block_n=lambda platform: 512,
    tune_candidates=(256, 512, 1024, 2048),
)
def min_argmin_pallas_backend(x: jnp.ndarray, c: jnp.ndarray, *,
                              metric: str = "l2sq", block_n: int = 512,
                              interpret: bool = False):
    from . import kernel as _kernel  # deferred: pallas import is optional
    return _kernel.min_argmin_pallas(x, c, metric=metric, bn=block_n,
                                     interpret=interpret)


def min_argmin(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    metric: str = "l2sq",
    policy: Optional[KernelPolicy] = None,
    block_n: Optional[int] = None,      # removed alias: raises TypeError
    use_pallas: Optional[bool] = None,  # removed alias: raises TypeError
):
    """For each row of ``x`` (n, d): distance to nearest row of ``c`` (m, d)
    and its index. Returns (dist (n,), idx (n,) int32).

    Backend/tile selection comes from ``policy`` (default: the process
    policy, see ``dispatch.set_default_policy``).  Resolution happens at
    trace time, so calls inside ``jax.jit`` cost nothing at runtime.
    """
    policy = dispatch.resolve_policy(policy, use_pallas=use_pallas,
                                     block_n=block_n, caller="min_argmin")
    n, d = x.shape
    reg, bn = dispatch.resolve("min_argmin", policy, metric=metric,
                               n=n, m=c.shape[0], d=d, dtype=x.dtype)
    return reg.impl(x, c, metric=metric, block_n=bn)
