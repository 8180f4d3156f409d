"""Unified kernel-dispatch layer: backend registry + autotuned block sizes.

Every compute hot-spot of the paper funnels through two ops — fused
nearest-center distance (``min_argmin``, Algorithm 1's ball-growing) and the
fused Lloyd step (``lloyd_step``, the coordinator's weighted k-means--).
Each op has several implementations (Pallas TPU kernel, chunked blocked
jnp, pure-jnp reference oracle) with different capabilities; historically
every caller hand-threaded ``use_pallas: bool`` + ``block_n: int`` and
re-implemented the same ``if use_pallas and metric in (...)`` dispatch
inline.  This module replaces that plumbing with:

* a **backend registry**: each implementation registers under a name
  (``"pallas"``, ``"blocked"``, ``"ref"``) with a capability predicate over
  (metric, platform, dtype, shape) and a platform-dependent auto-selection
  priority;
* one **``KernelPolicy``** frozen dataclass (backend, block_n, autotune) —
  the single object threaded through the algorithm layers, or installed
  process-wide with ``set_default_policy``.  ``backend="auto"`` picks the
  best supported implementation for the current platform (Pallas on TPU,
  blocked elsewhere) without the caller knowing.  An explicitly named
  backend is served as named or raises — it never falls back;
* an **autotuner** that benchmarks candidate ``block_n`` tile sizes per
  (op, backend, metric, shape-bucket, platform) and caches the winner in a
  JSON file under ``~/.cache/repro_kernels/`` (override the location with
  ``$REPRO_KERNELS_CACHE``), so CPU blocked paths and TPU Pallas paths each
  get measured tiles instead of one hard-coded constant.

Resolution happens at trace time (shapes are concrete under ``jax.jit``),
so a jitted caller taking ``policy`` as a static argument compiles exactly
one registry decision per (shape, policy) — no runtime branching.

The pre-registry ``use_pallas=``/``block_n=`` keyword aliases survived one
release as deprecated warnings at the public API edges; they are now
removed — :func:`resolve_policy` raises a ``TypeError`` pointing at
``KernelPolicy`` when either is passed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import logging
import os
import time
from pathlib import Path
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import numpy as np

from repro import obs

_log = logging.getLogger("repro.kernels.dispatch")

# "int8" is the quantized-center score backend: it changes results (bounded
# quantization error, measured in benchmarks/stream_bench.py), so it is
# never auto-picked — callers must name it explicitly.
BACKENDS = ("auto", "pallas", "blocked", "ref", "int8")

OPS = ("min_argmin", "lloyd_step", "score")


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """The one kernel-selection object threaded through the algorithm layers.

    backend   — "auto" (pick per platform/capability), or an explicit
                registry name.  An explicit backend that cannot serve a
                particular call (e.g. the Pallas lloyd kernel under the l1
                metric, or the Pallas kernels off a TPU) raises.
    block_n   — row-tile size; None means "backend default, or autotuned
                when ``autotune`` is set".
    autotune  — measure candidate block_n values for this op/shape-bucket
                (cached on disk) instead of using the backend default.
    interpret — run the Pallas kernels in the Pallas interpreter (needs
                ``backend="pallas"``): how tests check the kernels on a
                host without a TPU.  Never chosen implicitly.
    """

    backend: str = "auto"
    block_n: Optional[int] = None
    autotune: bool = False
    interpret: bool = False

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {BACKENDS}")
        bn = self.block_n
        if bn is not None and (not isinstance(bn, int)
                               or isinstance(bn, bool) or bn < 1):
            raise ValueError(
                f"block_n must be None or an int >= 1, got {bn!r}")
        if self.interpret and self.backend != "pallas":
            raise ValueError(
                f"interpret=True runs the Pallas interpreter and needs "
                f"backend='pallas', got backend={self.backend!r}")


class Registration(NamedTuple):
    """One backend implementation of one op.

    2-D ops (the fused ``score`` path) additionally register a center-tile
    dimension: ``default_block_m`` (platform -> int) plus
    ``tune_candidates_m``, and their ``impl`` accepts a ``block_m``
    keyword.  1-D ops leave both at their defaults and resolve through
    :func:`resolve` exactly as before.
    """

    op: str
    name: str
    impl: Callable                 # op-specific signature, kw block_n
    supports: Callable             # (metric, platform, dtype, n, m, d) -> bool
    priority: Callable             # platform -> int; < 0 means never auto-picked
    default_block_n: Callable      # platform -> int
    tune_candidates: tuple         # candidate block_n values for the autotuner
    make_args: Callable            # (n, m, d, rng) -> positional args for impl
    default_block_m: Optional[Callable] = None   # platform -> int (2-D ops)
    tune_candidates_m: tuple = ()  # candidate block_m values (2-D ops)


_REGISTRY: dict[str, dict[str, Registration]] = {}
_default_policy = KernelPolicy()
_registered = False


def _ensure_registered() -> None:
    """Import the op modules so their backends land in the registry."""
    global _registered
    if _registered:
        return
    _registered = True
    from repro.kernels.lloyd import ops as _lloyd_ops   # noqa: F401
    from repro.kernels.pdist import ops as _pdist_ops   # noqa: F401
    from repro.kernels.score import ops as _score_ops   # noqa: F401


def register(
    op: str,
    name: str,
    *,
    supports: Callable,
    priority: Callable,
    default_block_n: Callable,
    tune_candidates: Sequence[int] = (),
    make_args: Callable = None,
    default_block_m: Callable = None,
    tune_candidates_m: Sequence[int] = (),
):
    """Decorator: register ``fn`` as the ``name`` backend of ``op``."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; expected one of {OPS}")

    def deco(fn):
        _REGISTRY.setdefault(op, {})[name] = Registration(
            op=op, name=name, impl=fn, supports=supports, priority=priority,
            default_block_n=default_block_n,
            tune_candidates=tuple(tune_candidates),
            make_args=make_args,
            default_block_m=default_block_m,
            tune_candidates_m=tuple(tune_candidates_m))
        return fn

    return deco


def registered_backends(op: str) -> dict[str, Registration]:
    _ensure_registered()
    if op not in _REGISTRY:
        raise ValueError(f"no backends registered for op {op!r}")
    return _REGISTRY[op]


# --------------------------------------------------------------- policy state
def get_default_policy() -> KernelPolicy:
    return _default_policy


def set_default_policy(policy: KernelPolicy) -> KernelPolicy:
    """Install ``policy`` process-wide; returns the previous default."""
    global _default_policy
    prev = _default_policy
    _default_policy = policy
    return prev


@contextlib.contextmanager
def using_policy(policy: KernelPolicy):
    """Context manager: scoped :func:`set_default_policy`."""
    prev = set_default_policy(policy)
    try:
        yield policy
    finally:
        set_default_policy(prev)


def resolve_policy(
    policy: Optional[KernelPolicy] = None,
    *,
    use_pallas: Optional[bool] = None,
    block_n: Optional[int] = None,
    caller: str = "",
) -> KernelPolicy:
    """Resolve ``policy`` (default: the process policy) at a public edge.

    The pre-registry ``use_pallas=``/``block_n=`` keyword aliases were
    deprecated for one release and are now removed: passing either raises
    a ``TypeError`` naming the replacement (``use_pallas=True`` was
    ``KernelPolicy(backend="pallas")``; ``block_n=N`` was
    ``KernelPolicy(backend="blocked", block_n=N)``).
    """
    if use_pallas is not None or block_n is not None:
        raise TypeError(
            f"{caller or 'kernel op'}: the use_pallas=/block_n= keyword "
            f"aliases were removed; pass "
            f"policy=KernelPolicy(backend=..., block_n=...) or install a "
            f"process default with set_default_policy()")
    return policy if policy is not None else get_default_policy()


# ----------------------------------------------------------------- resolution
def select_backend(
    op: str,
    policy: Optional[KernelPolicy] = None,
    *,
    metric: str,
    n: int,
    m: int,
    d: int,
    dtype=np.float32,
    platform: Optional[str] = None,
) -> Registration:
    """Pick the registration serving this call under ``policy``.

    An explicit backend that cannot serve the call raises: a run that
    named Pallas must not quietly measure or serve something else."""
    policy = policy if policy is not None else get_default_policy()
    platform = platform or jax.default_backend()
    regs = registered_backends(op)
    if policy.backend != "auto":
        reg = regs.get(policy.backend)
        if reg is None:
            raise ValueError(
                f"op {op!r} has no backend {policy.backend!r}; "
                f"registered: {sorted(regs)}")
        if not reg.supports(metric, platform, dtype, n, m, d):
            raise ValueError(
                f"backend {policy.backend!r} cannot serve op {op!r} with "
                f"metric={metric!r} on platform {platform!r} for shape "
                f"(n={n}, m={m}, d={d}); name a backend that can, or 'auto'")
        if reg.name == "pallas":
            if policy.interpret:
                return reg._replace(
                    impl=functools.partial(reg.impl, interpret=True))
            if platform != "tpu":
                raise ValueError(
                    f"backend 'pallas' compiles for a TPU and this process "
                    f"runs on {platform!r}; run on a TPU, or ask for the "
                    f"interpreter with KernelPolicy(backend='pallas', "
                    f"interpret=True)")
        return reg
    candidates = [
        r for r in regs.values()
        if r.priority(platform) >= 0
        and r.supports(metric, platform, dtype, n, m, d)
    ]
    if not candidates:
        raise ValueError(
            f"no backend of op {op!r} supports metric={metric!r} on "
            f"platform {platform!r} for shape (n={n}, m={m}, d={d})")
    return max(candidates, key=lambda r: r.priority(platform))


def resolve(
    op: str,
    policy: Optional[KernelPolicy] = None,
    *,
    metric: str,
    n: int,
    m: int,
    d: int,
    dtype=np.float32,
    platform: Optional[str] = None,
) -> tuple[Registration, int]:
    """Registry lookup: (registration, block_n) for one concrete call."""
    policy = policy if policy is not None else get_default_policy()
    platform = platform or jax.default_backend()
    reg = select_backend(op, policy, metric=metric, n=n, m=m, d=d,
                         dtype=dtype, platform=platform)
    # resolution happens at trace time, so under jit this counts compiled
    # registry decisions (one per shape/policy), not per-element calls
    obs.counter("kernels.dispatch", op=op, backend=reg.name).inc()
    bn = policy.block_n
    if bn is None:
        if policy.autotune and reg.tune_candidates:
            bn = autotune_block_n(op, reg.name, metric=metric, n=n, m=m, d=d,
                                  platform=platform)
        else:
            bn = reg.default_block_n(platform)
    return reg, int(bn)


def resolve_tiles(
    op: str,
    policy: Optional[KernelPolicy] = None,
    *,
    metric: str,
    n: int,
    m: int,
    d: int,
    dtype=np.float32,
    platform: Optional[str] = None,
) -> tuple[Registration, int, int]:
    """Registry lookup for a 2-D-tiled op: (registration, block_n, block_m).

    Like :func:`resolve`, but also resolves the center-tile size for ops
    registered with ``default_block_m``.  An explicit ``policy.block_n``
    pins the row tile (and disables the joint tuner — exactly the 1-D
    semantics); otherwise, under ``policy.autotune``, the (block_n,
    block_m) pair is measured *jointly* per shape bucket and cached.
    """
    policy = policy if policy is not None else get_default_policy()
    platform = platform or jax.default_backend()
    reg = select_backend(op, policy, metric=metric, n=n, m=m, d=d,
                         dtype=dtype, platform=platform)
    obs.counter("kernels.dispatch", op=op, backend=reg.name).inc()
    if reg.default_block_m is None:
        # a 1-D backend serving a 2-D op entry point: column tile unused
        bn = policy.block_n
        if bn is None:
            bn = (autotune_block_n(op, reg.name, metric=metric, n=n, m=m,
                                   d=d, platform=platform)
                  if policy.autotune and reg.tune_candidates
                  else reg.default_block_n(platform))
        return reg, int(bn), 0
    bn, bm = policy.block_n, None
    if bn is None and policy.autotune and reg.tune_candidates:
        bn, bm = autotune_tiles(op, reg.name, metric=metric, n=n, m=m, d=d,
                                platform=platform)
    if bn is None:
        bn = reg.default_block_n(platform)
    if bm is None:
        bm = reg.default_block_m(platform)
    return reg, int(bn), int(bm)


# ------------------------------------------------------------------ autotuner
# v2: 2-D ops cache the jointly-tuned (block_n, block_m) pair.  The bump
# changes the key prefix, so pre-bump entries simply never match — and any
# entry that *does* match a key but lacks the fields its reader needs
# (e.g. a single-block_n record left under a 2-D op's key) is skipped with
# a debug log and re-measured, never a KeyError.
_TUNE_VERSION = 2
# Shapes at/above this row bucket share one measurement (bounds tuner cost).
_MAX_MEASURE_ROWS = 1 << 17
_tune_cache: Optional[dict] = None
_tuning = False   # re-entrancy guard: the measurement itself calls resolve()


def cache_dir() -> Path:
    return Path(os.environ.get(
        "REPRO_KERNELS_CACHE", "~/.cache/repro_kernels")).expanduser()


def _cache_path() -> Path:
    return cache_dir() / "autotune.json"


def _bucket(v: int, lo: int = 1) -> int:
    b = max(lo, 1)
    while b < v:
        b <<= 1
    return b


def _load_cache() -> dict:
    global _tune_cache
    if _tune_cache is None:
        try:
            _tune_cache = json.loads(_cache_path().read_text())
        except (OSError, ValueError):
            _tune_cache = {}
        stale = [k for k in _tune_cache
                 if not k.startswith(f"v{_TUNE_VERSION}/")]
        if stale:
            _log.debug("autotune cache %s holds %d entr%s from older schema "
                       "versions (e.g. %s); they are ignored, not migrated",
                       _cache_path(), len(stale),
                       "y" if len(stale) == 1 else "ies", stale[0])
    return _tune_cache


def _cache_hit(key: str, required: Sequence[str]) -> Optional[dict]:
    """Cached entry for ``key`` iff it carries every ``required`` field.

    A matching key with missing fields (a stale single-``block_n`` record
    under a 2-D op's key, or a hand-edited file) is skipped with a debug
    log and re-measured — the schema bump must never surface as a
    KeyError in a caller.
    """
    hit = _load_cache().get(key)
    if not isinstance(hit, dict):
        return None
    missing = [f for f in required if f not in hit]
    if missing:
        _log.debug("stale autotune entry %s (missing %s); re-measuring",
                   key, ", ".join(missing))
        return None
    return hit


def _store_cache(key: str, entry: dict) -> None:
    cache = _load_cache()
    cache[key] = entry
    try:
        cache_dir().mkdir(parents=True, exist_ok=True)
        tmp = _cache_path().with_suffix(".tmp")
        tmp.write_text(json.dumps(cache, indent=2, sort_keys=True) + "\n")
        tmp.replace(_cache_path())
    except OSError:
        pass   # cache is an optimization; never fail the caller over it


def clear_autotune_cache(*, on_disk: bool = False) -> None:
    """Drop the in-memory autotune cache (and optionally the JSON file)."""
    global _tune_cache
    _tune_cache = None
    if on_disk:
        try:
            _cache_path().unlink()
        except OSError:
            pass


def _default_make_args(n: int, m: int, d: int, rng: np.random.Generator):
    x = rng.standard_normal((n, d)).astype(np.float32)
    c = rng.standard_normal((m, d)).astype(np.float32)
    return (x, c)


def _time_call(fn, *, repeats: int) -> float:
    out = fn()                       # compile + warm outside the clock
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def measure_block_ns(
    op: str,
    backend: str,
    *,
    metric: str,
    n: int,
    m: int,
    d: int,
    candidates: Optional[Sequence[int]] = None,
    repeats: int = 3,
) -> dict[int, float]:
    """Time ``op``'s ``backend`` impl at each candidate block_n (seconds)."""
    reg = registered_backends(op)[backend]
    cands = list(candidates if candidates is not None else reg.tune_candidates)
    if not cands:
        cands = [reg.default_block_n(jax.default_backend())]
    rng = np.random.default_rng(0)
    make = reg.make_args or _default_make_args
    args = make(n, m, d, rng)
    return {bn: _time_call(
        functools.partial(reg.impl, *args, metric=metric, block_n=bn),
        repeats=repeats) for bn in cands}


def measure_tiles(
    op: str,
    backend: str,
    *,
    metric: str,
    n: int,
    m: int,
    d: int,
    candidates: Sequence[tuple[int, int]],
    repeats: int = 3,
) -> dict[tuple[int, int], float]:
    """Time a 2-D op's impl at each candidate (block_n, block_m) pair."""
    reg = registered_backends(op)[backend]
    rng = np.random.default_rng(0)
    make = reg.make_args or _default_make_args
    args = make(n, m, d, rng)
    return {(bn, bm): _time_call(
        functools.partial(reg.impl, *args, metric=metric,
                          block_n=bn, block_m=bm),
        repeats=repeats) for bn, bm in candidates}


def autotune_block_n(
    op: str,
    backend: str,
    *,
    metric: str,
    n: int,
    m: int,
    d: int,
    platform: Optional[str] = None,
    repeats: int = 3,
) -> int:
    """Best block_n for (op, backend, metric, shape-bucket, platform).

    Cached in ``cache_dir()/autotune.json``; one measurement per bucket.
    """
    global _tuning
    platform = platform or jax.default_backend()
    reg = registered_backends(op)[backend]
    if not reg.tune_candidates or _tuning:
        return reg.default_block_n(platform)
    bn_rows = min(_bucket(n), _MAX_MEASURE_ROWS)
    bm, bd = _bucket(m), _bucket(d)
    key = (f"v{_TUNE_VERSION}/{op}/{backend}/{platform}/{metric}/"
           f"n{bn_rows}/m{bm}/d{bd}")
    hit = _cache_hit(key, ("block_n",))
    if hit is not None:
        obs.counter("kernels.autotune_cache", result="hit").inc()
        return int(hit["block_n"])
    obs.counter("kernels.autotune_cache", result="miss").inc()
    _tuning = True
    try:
        with obs.trace("kernels.autotune", op=op, backend=backend):
            cands = sorted({min(c, bn_rows) for c in reg.tune_candidates})
            timings = measure_block_ns(op, backend, metric=metric, n=bn_rows,
                                       m=bm, d=bd, candidates=cands,
                                       repeats=repeats)
    finally:
        _tuning = False
    best = min(timings, key=timings.get)
    _store_cache(key, {
        "block_n": int(best),
        "timings_us": {str(bn): round(t * 1e6, 2)
                       for bn, t in timings.items()},
        "measured_shape": [bn_rows, bm, bd],
    })
    return int(best)


def autotune_tiles(
    op: str,
    backend: str,
    *,
    metric: str,
    n: int,
    m: int,
    d: int,
    platform: Optional[str] = None,
    repeats: int = 3,
) -> tuple[int, int]:
    """Best jointly-tuned (block_n, block_m) pair for a 2-D op.

    The candidate grid is the cross product of the backend's registered
    row-tile and center-tile candidates (each clipped to its shape bucket
    — no point tiling wider than the data); tiles interact through cache
    and VMEM residency, so the pair is measured together rather than each
    dimension in isolation.  Shares the v2 cache keyspace with
    :func:`autotune_block_n`; an entry lacking ``block_m`` (written by the
    1-D tuner for the same bucket) is treated as stale and re-measured.
    """
    global _tuning
    platform = platform or jax.default_backend()
    reg = registered_backends(op)[backend]
    if reg.default_block_m is None:
        raise ValueError(f"op {op!r} backend {backend!r} registered no "
                         f"block_m dimension; use autotune_block_n")
    if not reg.tune_candidates or _tuning:
        return (reg.default_block_n(platform), reg.default_block_m(platform))
    bn_rows = min(_bucket(n), _MAX_MEASURE_ROWS)
    bm_cols, bd = _bucket(m), _bucket(d)
    key = (f"v{_TUNE_VERSION}/{op}/{backend}/{platform}/{metric}/"
           f"n{bn_rows}/m{bm_cols}/d{bd}")
    hit = _cache_hit(key, ("block_n", "block_m"))
    if hit is not None:
        obs.counter("kernels.autotune_cache", result="hit").inc()
        return int(hit["block_n"]), int(hit["block_m"])
    obs.counter("kernels.autotune_cache", result="miss").inc()
    _tuning = True
    try:
        with obs.trace("kernels.autotune", op=op, backend=backend):
            bns = sorted({min(c, bn_rows) for c in reg.tune_candidates})
            bms = sorted({min(c, bm_cols) for c in (reg.tune_candidates_m
                                                    or (reg.default_block_m(
                                                        platform),))})
            pairs = [(bn, bm) for bn in bns for bm in bms]
            timings = measure_tiles(op, backend, metric=metric, n=bn_rows,
                                    m=bm_cols, d=bd, candidates=pairs,
                                    repeats=repeats)
    finally:
        _tuning = False
    best = min(timings, key=timings.get)
    _store_cache(key, {
        "block_n": int(best[0]),
        "block_m": int(best[1]),
        "timings_us": {f"{bn}x{bm}": round(t * 1e6, 2)
                       for (bn, bm), t in timings.items()},
        "measured_shape": [bn_rows, bm_cols, bd],
    })
    return int(best[0]), int(best[1])
