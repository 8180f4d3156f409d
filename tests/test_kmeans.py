"""Second-level clustering (k-means--) + baseline summaries."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # optional: only the property tests need hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import (kmeans_minus_minus, kmeans_mm, kmeanspp_summary,
                        pp_budget, kmeans_parallel_summary, rand_summary)
from repro.data.synthetic import gauss


def test_kmeans_mm_finds_planted_outliers():
    x, out_ids = gauss(n_centers=5, per_center=400, t=25, sigma=0.05, seed=0)
    n = x.shape[0]
    sol = kmeans_minus_minus(jnp.asarray(x), jnp.ones((n,)), jnp.ones((n,), bool),
                             jax.random.key(0), k=5, t=25.0)
    found = set(np.nonzero(np.asarray(sol.outlier))[0].tolist())
    rec = len(found & set(out_ids.tolist())) / len(out_ids)
    assert rec >= 0.8


def test_kmeans_mm_outlier_budget_respected():
    x, _ = gauss(n_centers=4, per_center=200, t=20, sigma=0.1, seed=1)
    n = x.shape[0]
    w = jnp.ones((n,))
    sol = kmeans_minus_minus(jnp.asarray(x), w, jnp.ones((n,), bool),
                             jax.random.key(0), k=4, t=20.0)
    assert float((w * sol.outlier).sum()) <= 20.0


def test_weighted_equals_duplicated():
    """A point with weight w must act like w coincident unit points."""
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(50, 3)).astype(np.float32)
    w = rng.integers(1, 4, size=50).astype(np.float32)
    dup = np.repeat(pts, w.astype(int), axis=0)
    key = jax.random.key(7)
    s1 = kmeans_minus_minus(jnp.asarray(pts), jnp.asarray(w),
                            jnp.ones((50,), bool), key, k=3, t=5.0, iters=30)
    s2 = kmeans_minus_minus(jnp.asarray(dup), jnp.ones((dup.shape[0],)),
                            jnp.ones((dup.shape[0],), bool), key, k=3, t=5.0,
                            iters=30)
    assert abs(float(s1.cost) - float(s2.cost)) / max(float(s2.cost), 1e-6) < 0.35


def test_pp_summary_weights_conserve():
    x = np.random.default_rng(0).normal(size=(1000, 4)).astype(np.float32)
    b = pp_budget(1000, 5, 20)
    s = kmeanspp_summary(jnp.asarray(x), jax.random.key(0), budget=b)
    np.testing.assert_allclose(float(s.weights.sum()), 1000, rtol=1e-6)
    assert int(s.valid.sum()) == b


def test_rand_summary_weights_conserve():
    x = np.random.default_rng(0).normal(size=(800, 4)).astype(np.float32)
    s = rand_summary(jnp.asarray(x), jax.random.key(0), budget=100)
    np.testing.assert_allclose(float(s.weights.sum()), 800, rtol=1e-6)
    assert len(np.unique(np.asarray(s.indices))) == 100  # no replacement


def test_kmeans_parallel_comm_grows_with_sites():
    x = np.random.default_rng(0).normal(size=(2000, 4)).astype(np.float32)
    r5 = kmeans_parallel_summary(jnp.asarray(x), jax.random.key(0),
                                 budget=100, sites=5)
    r20 = kmeans_parallel_summary(jnp.asarray(x), jax.random.key(0),
                                  budget=100, sites=20)
    assert float(r20.comm_records) > 3.0 * float(r5.comm_records)
    np.testing.assert_allclose(float(r5.summary.weights.sum()), 2000, rtol=1e-6)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(k=st.integers(1, 8), t=st.integers(0, 30), seed=st.integers(0, 10**6))
    def test_kmeans_mm_property(k, t, seed):
        rng = np.random.default_rng(seed)
        n = 300
        x = rng.normal(size=(n, 3)).astype(np.float32)
        sol = kmeans_minus_minus(jnp.asarray(x), jnp.ones((n,)),
                                 jnp.ones((n,), bool), jax.random.key(seed % 97),
                                 k=k, t=float(t), iters=10)
        assert sol.centers.shape == (k, 3)
        assert float(jnp.sum(sol.outlier)) <= t
        assert np.isfinite(float(sol.cost))
        assert float(sol.cost) >= 0
else:
    def test_kmeans_mm_property():
        pytest.importorskip("hypothesis")


# ----- outlier marking: sorts only, bit-identical to argsort+gather+scatter --


def _mark_outliers_gather_scatter(dist, w_eff, t):
    """The argsort + gather + scatter marking the sort-only one replaced."""
    order = jnp.argsort(-dist)
    cumw = jnp.cumsum(w_eff[order])
    out_sorted = (cumw <= t) & (w_eff[order] > 0)
    return jnp.zeros_like(out_sorted).at[order].set(out_sorted)


def _marking_case(n, weights, seed):
    """Distances with exact ties (few distinct values, duplicated records)
    and -inf padding rows; weights with zeros, whole or fractional."""
    rng = np.random.default_rng(seed)
    dist = rng.integers(0, max(2, n // 8), size=n).astype(np.float32)
    dist[rng.random(n) < 0.1] = -np.inf
    if weights == "whole":
        w = rng.integers(0, 4, size=n).astype(np.float32)
    else:
        w = rng.uniform(0.0, 2.0, size=n).astype(np.float32)
        w[rng.random(n) < 0.2] = 0.0
    return jnp.asarray(dist), jnp.asarray(w)


@pytest.mark.parametrize("budget", ["zero", "boundary", "inside", "total"])
@pytest.mark.parametrize("weights", ["whole", "fractional"])
@pytest.mark.parametrize("n", [1, 7, 4096, 70000])
def test_mark_outliers_matches_gather_scatter(n, weights, budget):
    dist, w = _marking_case(n, weights, seed=n)
    order = jnp.argsort(-dist)
    cumw = np.asarray(jnp.cumsum(w[order]))
    t = {"zero": 0.0,
         "boundary": cumw[n // 2],            # exactly a cumulative weight
         "inside": 0.37 * float(cumw[-1]),
         "total": float(cumw[-1]) + 1.0}[budget]
    t = jnp.float32(t)
    got = jax.jit(kmeans_mm._mark_outliers)(dist, w, t)
    want = jax.jit(_mark_outliers_gather_scatter)(dist, w, t)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mark_outliers_lowers_without_gather_or_scatter():
    n = 4096
    args = (jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.float32(8.0))
    old = jax.jit(_mark_outliers_gather_scatter).lower(*args).as_text()
    assert "stablehlo.gather" in old and "stablehlo.scatter" in old
    new = jax.jit(kmeans_mm._mark_outliers).lower(*args).as_text()
    assert "stablehlo.gather" not in new
    assert "stablehlo.scatter" not in new


def test_kmeans_mm_output_unchanged_by_sort_only_marking(monkeypatch):
    """The whole solve (centers, assignment, outliers, cost) is bit-identical
    whichever marking the Lloyd loop traces."""
    x, _ = gauss(n_centers=4, per_center=300, t=30, sigma=0.1, seed=5)
    n = x.shape[0]
    w = np.random.default_rng(5).integers(1, 4, size=n).astype(np.float32)
    valid = np.ones((n,), bool)
    valid[-17:] = False

    def solve():
        jax.clear_caches()   # retrace with the module's current marking
        return kmeans_minus_minus(jnp.asarray(x), jnp.asarray(w),
                                  jnp.asarray(valid), jax.random.key(3),
                                  k=4, t=30.0, iters=12)

    new = solve()
    monkeypatch.setattr(kmeans_mm, "_mark_outliers",
                        _mark_outliers_gather_scatter)
    old = solve()
    monkeypatch.undo()
    jax.clear_caches()
    assert int(np.asarray(old.outlier).sum()) > 0
    for field in ("centers", "assignment", "outlier", "cost"):
        np.testing.assert_array_equal(np.asarray(getattr(new, field)),
                                      np.asarray(getattr(old, field)))
