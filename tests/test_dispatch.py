"""Kernel-dispatch layer: backend registry, policy threading, autotuner.

Parity contract (the acceptance bar for any new backend):
  * ``blocked`` == ``ref`` EXACTLY (backend vs backend, both through the
    registry) wherever the arithmetic order is preserved: the single-block
    shortcut (any metric) and the l1 center-chunking path in
    ``kernels/pdist/ops.py`` (pure adds, same order).  The tiled l2/l2sq
    path reassociates the matmul (XLA tiles a (64, d) block differently
    from the full array), so there the contract is distances within one
    float ulp-scale tolerance and bit-equal argmins;
  * ``pallas`` (in the Pallas interpreter on CPU, asked for by name with
    ``interpret=True``) matches ``ref`` within float tolerance, with
    identical argmins away from ties.

Plus: auto selection picks blocked off-TPU, an explicitly named backend
that cannot serve a call raises instead of falling back, the process-wide
default policy threads into jitted callers, the autotuner caches its
measured ``block_n`` under ``$REPRO_KERNELS_CACHE``, and the removed
``use_pallas=``/``block_n=`` aliases raise a ``TypeError`` pointing at
``KernelPolicy`` from every public edge.
"""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy
from repro.kernels.lloyd.ops import lloyd_step
from repro.kernels.lloyd.ref import lloyd_step_ref
from repro.kernels.pdist.ops import min_argmin
from repro.kernels.pdist.ref import min_argmin_ref
from repro.kernels.score.ops import score, score_blocked, score_int8

METRICS = ["l2sq", "l2", "l1"]
# ragged on purpose: nothing divides the tile sizes; the 200-center cases
# exercise the l1 center-chunking scan (mc=64) in the blocked path
RAGGED_SHAPES = [(37, 3, 5), (257, 65, 11), (1001, 200, 18), (130, 129, 3)]
# off a TPU the Pallas kernels run only in the interpreter, asked for by name
_INTERPRET = KernelPolicy(backend="pallas", interpret=True)


def _data(n, m, d):
    rng = np.random.default_rng(n * 7 + m * 3 + d)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 2.0, size=(n,)), jnp.float32)
    return x, c, w


# ------------------------------------------------------------ parity sweeps
@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("metric", METRICS)
def test_min_argmin_blocked_single_block_equals_ref_exactly(shape, metric):
    n, m, d = shape
    x, c, _ = _data(n, m, d)
    db, ab = min_argmin(x, c, metric=metric,
                        policy=KernelPolicy(backend="blocked",
                                            block_n=max(n, 16384)))
    dr, ar = min_argmin(x, c, metric=metric,
                        policy=KernelPolicy(backend="ref"))
    assert (np.asarray(db) == np.asarray(dr)).all()
    assert (np.asarray(ab) == np.asarray(ar)).all()
    # and the registered ref backend IS the oracle
    do, ao = min_argmin_ref(x, c, metric)
    np.testing.assert_allclose(np.asarray(dr), np.asarray(do),
                               rtol=1e-6, atol=1e-6)
    assert (np.asarray(ar) == np.asarray(ao)).all()


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("metric", METRICS)
def test_min_argmin_blocked_chunked_equals_ref(shape, metric):
    n, m, d = shape
    x, c, _ = _data(n, m, d)
    # block_n smaller than n: the chunked lax.map path, not the single-block
    # shortcut; m > 64 cases also exercise the l1 center-chunking scan
    db, ab = min_argmin(x, c, metric=metric,
                        policy=KernelPolicy(backend="blocked", block_n=64))
    dr, ar = min_argmin_ref(x, c, metric)
    assert (np.asarray(ab) == np.asarray(ar)).all()
    if metric == "l1":
        # pure adds in the same order: tiling cannot change the bits
        assert (np.asarray(db) == np.asarray(dr)).all()
    else:
        np.testing.assert_allclose(np.asarray(db), np.asarray(dr),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("metric", METRICS)
def test_min_argmin_pallas_interpret_close_to_ref(shape, metric):
    n, m, d = shape
    x, c, _ = _data(n, m, d)
    dp, ap_ = min_argmin(x, c, metric=metric, policy=_INTERPRET)
    dr, ar = min_argmin_ref(x, c, metric)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(ap_) == np.asarray(ar)).all()


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("metric", METRICS)
def test_lloyd_blocked_equals_ref(shape, metric):
    n, m, d = shape
    x, c, w = _data(n, m, d)
    sb, cb, ab, db = lloyd_step(x, w, c, metric=metric,
                                policy=KernelPolicy(backend="blocked",
                                                    block_n=64))
    sr, cr, ar, dr = lloyd_step_ref(x, w, c, metric)
    assert (np.asarray(ab) == np.asarray(ar)).all()
    if metric == "l1":
        assert (np.asarray(db) == np.asarray(dr)).all()
    else:
        np.testing.assert_allclose(np.asarray(db), np.asarray(dr),
                                   rtol=1e-5, atol=1e-5)
    # accumulators: one-hot matmul vs scatter-add differ only in summation
    # order
    np.testing.assert_allclose(np.asarray(sb), np.asarray(sr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cb), np.asarray(cr),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("metric", ["l2sq", "l2"])
def test_lloyd_pallas_interpret_close_to_ref(metric):
    x, c, w = _data(513, 37, 9)
    sp, cp, ap_, dp = lloyd_step(x, w, c, metric=metric, policy=_INTERPRET)
    sr, cr, ar, dr = lloyd_step_ref(x, w, c, metric)
    assert (np.asarray(ap_) == np.asarray(ar)).all()
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(cp), np.asarray(cr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ registry rules
def test_auto_selects_blocked_off_tpu():
    assert jax.default_backend() != "tpu", "test assumes a CPU/GPU host"
    for op in ("min_argmin", "lloyd_step", "score"):
        reg = dispatch.select_backend(op, KernelPolicy(), metric="l2sq",
                                      n=100, m=10, d=4)
        assert reg.name == "blocked"


def test_auto_on_tpu_prefers_pallas():
    reg = dispatch.select_backend("min_argmin", KernelPolicy(),
                                  metric="l2sq", n=100, m=10, d=4,
                                  platform="tpu")
    assert reg.name == "pallas"
    # ... but the lloyd kernel has no l1 path even on TPU
    reg = dispatch.select_backend("lloyd_step", KernelPolicy(),
                                  metric="l1", n=100, m=10, d=4,
                                  platform="tpu")
    assert reg.name == "blocked"


def test_explicit_unsupported_backend_falls_back():
    """An explicitly named backend that cannot serve a call no longer
    falls back to auto selection: it raises, naming the backend, the op
    and the platform.  So does Pallas off a TPU unless the interpreter is
    asked for by name."""
    with pytest.raises(ValueError, match="'pallas' cannot serve op "
                                         "'lloyd_step' with metric='l1'"):
        dispatch.select_backend("lloyd_step", KernelPolicy(backend="pallas"),
                                metric="l1", n=100, m=10, d=4,
                                platform="tpu")
    x, c, w = _data(64, 70, 5)
    with pytest.raises(ValueError, match="cannot serve"):
        lloyd_step(x, w, c, metric="l1", policy=_INTERPRET)
    for op in ("min_argmin", "lloyd_step", "score"):
        with pytest.raises(ValueError, match="compiles for a TPU"):
            dispatch.select_backend(op, KernelPolicy(backend="pallas"),
                                    metric="l2sq", n=100, m=10, d=4,
                                    platform="cpu")
        reg = dispatch.select_backend(op, _INTERPRET, metric="l2sq",
                                      n=100, m=10, d=4, platform="cpu")
        assert reg.name == "pallas"
    with pytest.raises(ValueError, match="compiles for a TPU"):
        min_argmin(x, c, policy=KernelPolicy(backend="pallas"))
    with pytest.raises(ValueError, match="needs backend='pallas'"):
        KernelPolicy(interpret=True)


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown backend"):
        KernelPolicy(backend="cuda")


def test_default_policy_threads_into_jitted_callers():
    from repro.core.summary import summary_outliers
    x = jnp.asarray(np.random.default_rng(0).normal(size=(400, 4)), jnp.float32)
    key = jax.random.key(3)
    base = summary_outliers(x, key, k=4, t=6)
    with dispatch.using_policy(KernelPolicy(backend="ref")):
        via_default = summary_outliers(x, key, k=4, t=6)
    # same sampling path, backend swap only: identical summaries
    assert (np.asarray(base.indices) == np.asarray(via_default.indices)).all()
    np.testing.assert_allclose(np.asarray(base.weights),
                               np.asarray(via_default.weights))
    # and the context manager restored the previous default
    assert dispatch.get_default_policy() == KernelPolicy()


def test_default_policy_not_frozen_by_jit_cache(monkeypatch):
    """Jitted entry points must re-resolve the process default per call: a
    policy=None static argument would freeze the first trace's backend into
    the compile cache (regression test for exactly that bug)."""
    from repro.core.rand_summary import rand_summary
    x = jnp.asarray(np.random.default_rng(2).normal(size=(128, 3)), jnp.float32)
    key = jax.random.key(0)
    rand_summary(x, key, budget=4)   # populate the jit cache under "blocked"

    calls = []
    regs = dispatch.registered_backends("min_argmin")
    orig = regs["ref"]

    def spy_impl(*args, **kw):
        calls.append("ref")
        return orig.impl(*args, **kw)

    monkeypatch.setitem(regs, "ref", orig._replace(impl=spy_impl))
    with dispatch.using_policy(KernelPolicy(backend="ref")):
        rand_summary(x, key, budget=4)   # same shapes: would cache-hit if stale
    assert calls, "default-policy switch ignored: jit cache served 'blocked'"


def test_configs_capture_process_default_at_construction():
    from repro.stream import ServiceConfig, ShardedServiceConfig, TreeConfig
    tuned = KernelPolicy(backend="ref", block_n=123)
    with dispatch.using_policy(tuned):
        svc_cfg = ServiceConfig(dim=3, k=4, t=10)
        sh_cfg = ShardedServiceConfig(dim=3, k=4, t=10, n_sites=2)
        tr_cfg = TreeConfig(dim=3, k=4, t=10)
    assert svc_cfg.policy == tuned
    assert svc_cfg.tree_config().policy == tuned
    assert sh_cfg.policy == tuned and sh_cfg.site_tree_config().policy == tuned
    assert tr_cfg.policy == tuned
    # an explicit policy always wins over the ambient default
    with dispatch.using_policy(tuned):
        explicit = ServiceConfig(dim=3, k=4, t=10,
                                 policy=KernelPolicy(backend="blocked"))
    assert explicit.policy == KernelPolicy(backend="blocked")


# ------------------------------------------------------------ autotuner
def test_autotune_writes_and_reuses_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS_CACHE", str(tmp_path))
    dispatch.clear_autotune_cache()
    try:
        bn = dispatch.autotune_block_n("min_argmin", "blocked",
                                       metric="l2sq", n=4096, m=16, d=4)
        assert bn in (4096, 8192, 16384, 32768, 65536)
        cache_file = tmp_path / "autotune.json"
        assert cache_file.exists()
        payload = json.loads(cache_file.read_text())
        (key,) = payload.keys()
        assert "min_argmin/blocked" in key
        assert payload[key]["block_n"] == bn
        assert payload[key]["timings_us"]
        # second call (same shape bucket): served from cache, so poisoning
        # the cached value must be reflected verbatim
        payload[key]["block_n"] = 12345
        cache_file.write_text(json.dumps(payload))
        dispatch.clear_autotune_cache()
        assert dispatch.autotune_block_n("min_argmin", "blocked",
                                         metric="l2sq", n=4000, m=16,
                                         d=4) == 12345
    finally:
        dispatch.clear_autotune_cache()


def test_autotune_policy_resolves_block_n(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS_CACHE", str(tmp_path))
    dispatch.clear_autotune_cache()
    try:
        # candidates above the shape bucket are clamped to it (no point
        # tiling wider than the data), so only {4096, 8192} compete here
        reg, bn = dispatch.resolve("min_argmin",
                                   KernelPolicy(autotune=True),
                                   metric="l2sq", n=5000, m=8, d=4)
        assert reg.name == "blocked" and bn in (4096, 8192)
        # an explicit block_n always wins over the tuner
        _, bn2 = dispatch.resolve("min_argmin",
                                  KernelPolicy(autotune=True, block_n=777),
                                  metric="l2sq", n=5000, m=8, d=4)
        assert bn2 == 777
    finally:
        dispatch.clear_autotune_cache()


# ------------------------------------------------------------ fused score op
_THR = 0.7  # scores land on both sides of the outlier boundary


def _score_ref(x, c, metric):
    """Oracle through the registry's ref backend (the composed path)."""
    return score(x, c, jnp.float32(_THR), metric=metric,
                 policy=KernelPolicy(backend="ref"))


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("metric", METRICS + ["cosine"])
def test_score_blocked_parity_vs_ref(shape, metric):
    n, m, d = shape
    x, c, _ = _data(n, m, d)
    dr, ar, sr = _score_ref(x, c, metric)
    # chunked rows (block_n=64) through the registry, default center tile
    db, ab, sb = score(x, c, jnp.float32(_THR), metric=metric,
                       policy=KernelPolicy(backend="blocked", block_n=64))
    assert (np.asarray(ab) == np.asarray(ar)).all()
    np.testing.assert_allclose(np.asarray(db), np.asarray(dr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sb), np.asarray(sr),
                               rtol=1e-5, atol=1e-5)
    # tiny center tile (block_m=32): the running-min scan over center
    # tiles, incl. the masked ragged last tile — bit-equal argmins still
    dt, at, st = score_blocked(x, c, jnp.float32(_THR), metric=metric,
                               block_n=64, block_m=32)
    assert (np.asarray(at) == np.asarray(ar)).all()
    np.testing.assert_allclose(np.asarray(dt), np.asarray(dr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("metric", METRICS)
def test_score_pallas_interpret_parity_vs_ref(shape, metric):
    n, m, d = shape
    x, c, _ = _data(n, m, d)
    dr, ar, sr = _score_ref(x, c, metric)
    dp, ap_, sp = score(x, c, jnp.float32(_THR), metric=metric,
                        policy=_INTERPRET)
    assert (np.asarray(ap_) == np.asarray(ar)).all()
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr),
                               rtol=1e-5, atol=1e-5)


def test_score_small_m_blocked_is_bit_identical_to_ref():
    # the serving shape (m = k ~ tens <= block_m): the center-tile loop
    # collapses to the ref computation, so fusing must not change a bit
    x, c, _ = _data(257, 20, 11)
    dr, ar, sr = _score_ref(x, c, "l2sq")
    db, ab, sb = score(x, c, jnp.float32(_THR), metric="l2sq",
                       policy=KernelPolicy(backend="blocked"))
    assert (np.asarray(db) == np.asarray(dr)).all()
    assert (np.asarray(ab) == np.asarray(ar)).all()
    assert (np.asarray(sb) == np.asarray(sr)).all()


def test_score_predicates_cosine_excluded_from_pallas_only():
    regs = dispatch.registered_backends("score")
    assert set(regs) == {"ref", "blocked", "pallas", "int8"}
    for name in ("ref", "blocked", "int8"):
        assert regs[name].supports("cosine", "cpu", np.float32, 100, 10, 4)
    assert not regs["pallas"].supports("cosine", "tpu", np.float32, 100, 10, 4)
    # explicit-but-unsupported raises, like pdist does; auto routes around
    with pytest.raises(ValueError, match="cannot serve"):
        dispatch.select_backend("score", KernelPolicy(backend="pallas"),
                                metric="cosine", n=100, m=10, d=4,
                                platform="tpu")
    reg = dispatch.select_backend("score", KernelPolicy(), metric="cosine",
                                  n=100, m=10, d=4, platform="tpu")
    assert reg.name == "blocked"


def test_score_int8_never_auto_picked():
    # int8 changes results, so auto must not select it on any platform
    for platform in ("cpu", "tpu"):
        reg = dispatch.select_backend("score", KernelPolicy(), metric="l2sq",
                                      n=100, m=10, d=4, platform=platform)
        assert reg.name != "int8"
    reg = dispatch.select_backend("score", KernelPolicy(backend="int8"),
                                  metric="l2sq", n=100, m=10, d=4)
    assert reg.name == "int8"


@pytest.mark.parametrize("metric", METRICS + ["cosine"])
def test_score_int8_error_within_gated_ceiling(metric):
    """The int8 path's error must stay under the SAME ceiling the bench
    gate enforces (benchmarks/stream_thresholds.json) — the bound is
    measured there, asserted here."""
    from pathlib import Path
    thr_file = (Path(__file__).resolve().parent.parent / "benchmarks"
                / "stream_thresholds.json")
    ceiling = json.loads(thr_file.read_text())["quant_max_score_err"]
    x, c, _ = _data(1001, 64, 8)
    dr, ar, _ = _score_ref(x, c, metric)
    # decision-boundary threshold, like the bench: scores sit around 1
    thr = jnp.maximum(jnp.median(dr), 1e-12).astype(jnp.float32)
    _, _, sr = score(x, c, thr, metric=metric,
                     policy=KernelPolicy(backend="ref"))
    _, _, sq = score(x, c, thr, metric=metric,
                     policy=KernelPolicy(backend="int8"))
    err = float(np.max(np.abs(np.asarray(sq) - np.asarray(sr))))
    assert err <= ceiling, (metric, err)


def test_score_joint_autotune_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS_CACHE", str(tmp_path))
    dispatch.clear_autotune_cache()
    try:
        bn, bm = dispatch.autotune_tiles("score", "blocked", metric="l2sq",
                                         n=2048, m=256, d=8)
        cache_file = tmp_path / "autotune.json"
        payload = json.loads(cache_file.read_text())
        (key,) = payload.keys()
        assert key.startswith("v2/score/blocked/")
        assert payload[key]["block_n"] == bn
        assert payload[key]["block_m"] == bm
        assert payload[key]["timings_us"]
        # second call (same bucket): served from cache — poisoning the
        # cached pair must be reflected verbatim
        payload[key]["block_n"], payload[key]["block_m"] = 12345, 678
        cache_file.write_text(json.dumps(payload))
        dispatch.clear_autotune_cache()
        assert dispatch.autotune_tiles("score", "blocked", metric="l2sq",
                                       n=2000, m=250, d=8) == (12345, 678)
        # and resolve_tiles threads the tuned pair through the policy path
        reg, rbn, rbm = dispatch.resolve_tiles(
            "score", KernelPolicy(autotune=True), metric="l2sq",
            n=2000, m=250, d=8)
        assert reg.name == "blocked" and (rbn, rbm) == (12345, 678)
        # an explicit block_n pins the row tile and disables the tuner
        _, ebn, ebm = dispatch.resolve_tiles(
            "score", KernelPolicy(autotune=True, block_n=777),
            metric="l2sq", n=2000, m=250, d=8)
        assert ebn == 777 and ebm != 678
    finally:
        dispatch.clear_autotune_cache()


def test_autotune_cache_ignores_stale_and_older_schema_entries(
        tmp_path, monkeypatch):
    """Schema-bump migration: a mixed-version cache file must be read
    without a KeyError — pre-v2 keys never match, and a v2 key written
    without ``block_m`` (the 1-D tuner's record under a 2-D op's bucket)
    is re-measured, not trusted."""
    monkeypatch.setenv("REPRO_KERNELS_CACHE", str(tmp_path))
    dispatch.clear_autotune_cache()
    try:
        stale_key = "v2/score/blocked/cpu/l2sq/n2048/m256/d8"
        mixed = {
            # pre-bump schema: unversioned key, single dimension
            "score/blocked/cpu/l2sq/n2048/m256/d8": {"block_n": 99999},
            # v2 key lacking the field the 2-D reader needs
            stale_key: {"block_n": 4096},
        }
        cache_file = tmp_path / "autotune.json"
        cache_file.write_text(json.dumps(mixed))
        bn, bm = dispatch.autotune_tiles("score", "blocked", metric="l2sq",
                                         n=2048, m=256, d=8)
        # stale entry was re-measured and overwritten with the full pair
        payload = json.loads(cache_file.read_text())
        assert payload[stale_key]["block_n"] == bn
        assert payload[stale_key]["block_m"] == bm
        # the old-schema key survives untouched (ignored, not migrated)
        assert payload["score/blocked/cpu/l2sq/n2048/m256/d8"] == {
            "block_n": 99999}
        # clear_autotune_cache over the mixed file: in-memory drop + reload
        dispatch.clear_autotune_cache()
        assert dispatch.autotune_tiles("score", "blocked", metric="l2sq",
                                       n=2048, m=256, d=8) == (bn, bm)
        # the 1-D tuner never sees 2-D entries as stale: block_n suffices
        bn1 = dispatch.autotune_block_n("score", "blocked", metric="l2sq",
                                        n=2048, m=256, d=8)
        assert bn1 == bn
    finally:
        dispatch.clear_autotune_cache()


# ------------------------------------------------- removed legacy aliases
def test_removed_aliases_raise_type_error_at_every_public_edge():
    """The PR-3 deprecation window is over: every public edge that carried
    ``use_pallas=``/``block_n=`` now raises a TypeError that names the
    ``KernelPolicy`` replacement instead of warning."""
    from repro.core.augmented import augmented_summary_outliers
    from repro.core.kmeans_mm import kmeans_minus_minus
    from repro.core.summary import summary_outliers
    from repro.stream.weighted import weighted_summary_outliers

    x = jnp.asarray(np.random.default_rng(1).normal(size=(64, 3)), jnp.float32)
    w = jnp.ones((64,), jnp.float32)
    key = jax.random.key(11)
    edges = [
        lambda: summary_outliers(x, key, k=3, t=5, use_pallas=True),
        lambda: augmented_summary_outliers(x, key, k=3, t=8, block_n=64),
        lambda: kmeans_minus_minus(x, w, w > 0, key, k=3, t=5.0,
                                   use_pallas=False),
        lambda: weighted_summary_outliers(x, w, key, k=3, t=5, block_n=128),
        lambda: min_argmin(x, x[:4], block_n=128),
        lambda: lloyd_step(x, w, x[:4], use_pallas=True),
        lambda: score(x, x[:4], 1.0, block_n=128),
    ]
    for edge in edges:
        with pytest.raises(TypeError, match="KernelPolicy"):
            edge()


def test_policy_plus_alias_is_still_an_error():
    x, c, _ = _data(10, 2, 2)
    with pytest.raises(TypeError, match="removed"):
        min_argmin(x, c, policy=KernelPolicy(), block_n=64)


def test_kernel_policy_validates_block_n():
    for bad in (0, -1, True, 2.5):
        with pytest.raises(ValueError, match="block_n"):
            KernelPolicy(block_n=bad)
    assert KernelPolicy(block_n=None).block_n is None
    assert KernelPolicy(block_n=64).block_n == 64
