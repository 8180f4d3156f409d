"""Summarizer registry: protocol invariants parametrized over every
registered implementation, merge-then-reduce composability, default
bit-identity with the pre-registry call sites, and the cosine satellite."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # optional: only the property tests need hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import distributed_cluster, simulate_coordinator, sites_mesh
from repro.data.synthetic import gauss, partition, susy_like
from repro.kernels.pdist.ops import min_argmin
from repro.stream import (ServiceConfig, ShardedServiceConfig,
                          ShardedStreamService, StreamService, StreamTree,
                          TreeConfig)
from repro.stream.weighted import resummarize, weighted_summary_outliers
from repro.summarize import (SummarizerPolicy, get_summarizer, record_bound,
                             reduce_summaries, registered_summarizers,
                             select_summarizer, site_summary, summarize,
                             summarizer_policy, using_summarizer)
from repro.summarize.paper import pick_augmented

ALL_SUMMARIZERS = sorted(registered_summarizers())
K, T = 8, 25


def _data(n=1200, d=4, seed=0, outliers=30):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    if outliers:
        ids = rng.choice(n, outliers, replace=False)
        x[ids] += rng.uniform(-25, 25, size=(outliers, d)).astype(np.float32)
    return x


def _check_protocol(x, w, summ, t):
    # mass conservation: the contract that makes summaries compose
    np.testing.assert_allclose(float(summ.weights.sum()), float(w.sum()),
                               rtol=1e-4)
    assert float(summ.total_weight) == pytest.approx(float(w.sum()), rel=1e-5)
    # every record carries positive mass
    assert (summ.weights > 0).all()
    # provenance: summary points are input rows, ids into the caller's array
    assert summ.indices is not None
    np.testing.assert_array_equal(summ.points, x[summ.indices])
    # candidate (outlier-survivor) mass bounded by the paper's 8t
    assert float(summ.weights[summ.is_candidate].sum()) <= 8 * t + 1e-3


# ------------------------------------------------------------------ registry
def test_registry_contents():
    assert {"paper", "uniform", "ball_cover", "coreset"} <= set(ALL_SUMMARIZERS)
    with pytest.raises(ValueError, match="unknown summarizer"):
        get_summarizer("nope")
    with pytest.raises(ValueError, match="unknown summarizer"):
        summarize(np.zeros((4, 2)), np.ones(4), jax.random.key(0),
                  k=2, t=1, policy=SummarizerPolicy("nope"))


def test_auto_selects_paper_and_never_a_baseline():
    for metric in ("l2sq", "l2", "l1", "cosine"):
        spec = select_summarizer(SummarizerPolicy("auto"), metric=metric,
                                 k=K, t=T)
        assert spec.name == "paper"
    assert get_summarizer("uniform").priority < 0  # by-name only


def test_policy_params_are_canonical_and_hashable():
    a = summarizer_policy("coreset", budget=64, seed_rounds=2)
    b = SummarizerPolicy("coreset", {"seed_rounds": 2, "budget": 64})
    assert a == b and hash(a) == hash(b)
    assert a.with_params(budget=128).params_dict()["budget"] == 128
    assert a.params_dict() == {"budget": 64, "seed_rounds": 2}


# ------------------------------------------- protocol, every implementation
@pytest.mark.parametrize("name", ALL_SUMMARIZERS)
def test_protocol_unit_weights(name):
    x = _data()
    w = np.ones((x.shape[0],), np.float32)
    summ = summarize(x, w, jax.random.key(1), k=K, t=T,
                     policy=SummarizerPolicy(name))
    _check_protocol(x, w, summ, T)
    # unit weights: candidates each carry >= 1 mass, so count <= 8t too
    assert int(summ.is_candidate.sum()) <= 8 * T


@pytest.mark.parametrize("name", ALL_SUMMARIZERS)
def test_protocol_weighted_records(name):
    x = _data(seed=2)
    rng = np.random.default_rng(3)
    w = rng.uniform(0.25, 4.0, size=(x.shape[0],)).astype(np.float32)
    w[rng.choice(x.shape[0], 50, replace=False)] = 0.0  # dropped rows
    summ = summarize(x, w, jax.random.key(2), k=K, t=T,
                     policy=SummarizerPolicy(name))
    _check_protocol(x, w, summ, T)


@pytest.mark.parametrize("name", ALL_SUMMARIZERS)
def test_merge_then_reduce_composes(name):
    pol = SummarizerPolicy(name)
    x1, x2 = _data(seed=4), _data(seed=5)
    w = np.ones((x1.shape[0],), np.float32)
    s1 = summarize(x1, w, jax.random.key(3), k=K, t=T, policy=pol)
    s2 = summarize(x2, w, jax.random.key(4), k=K, t=T, policy=pol)
    red = reduce_summaries([s1, s2], jax.random.key(5), k=K, t=T, policy=pol)
    # reducing a union of summaries conserves the union's mass ...
    np.testing.assert_allclose(float(red.weights.sum()),
                               x1.shape[0] + x2.shape[0], rtol=1e-4)
    # ... stays within the registered static record bound ...
    cap = record_bound(pol, k=K, t=T, max_points=x1.shape[0] + x2.shape[0],
                       leaf_size=x1.shape[0])
    assert red.points.shape[0] <= cap
    # ... and keeps the candidate-mass bound (outliers can still surface)
    assert float(red.weights[red.is_candidate].sum()) <= 8 * T + 1e-3


@pytest.mark.parametrize("name", ALL_SUMMARIZERS)
def test_empty_and_degenerate_inputs(name):
    pol = SummarizerPolicy(name)
    s = summarize(np.zeros((0, 3), np.float32), np.zeros((0,), np.float32),
                  jax.random.key(0), k=K, t=T, policy=pol)
    assert s.points.shape[0] == 0 and s.total_weight == 0.0
    one = summarize(np.ones((1, 3), np.float32), np.ones((1,), np.float32),
                    jax.random.key(0), k=K, t=T, policy=pol)
    assert float(one.weights.sum()) == pytest.approx(1.0)


# ---------------------------------------------------- hypothesis properties
if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(
        name=st.sampled_from(ALL_SUMMARIZERS),
        n=st.integers(min_value=1, max_value=180),
        d=st.integers(min_value=1, max_value=6),
        t=st.integers(min_value=1, max_value=12),
        wseed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_mass_conservation_property(name, n, d, t, wseed):
        rng = np.random.default_rng(wseed)
        x = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.uniform(0.0, 3.0, size=(n,)).astype(np.float32)
        summ = summarize(x, w, jax.random.key(wseed % 997), k=3, t=t,
                         policy=SummarizerPolicy(name))
        np.testing.assert_allclose(float(summ.weights.sum()), float(w.sum()),
                                   rtol=1e-3, atol=1e-4)
        assert float(summ.weights[summ.is_candidate].sum()) <= 8 * t + 1e-3
        if summ.points.shape[0]:
            np.testing.assert_array_equal(summ.points, x[summ.indices])

    @settings(max_examples=8, deadline=None)
    @given(
        name=st.sampled_from(ALL_SUMMARIZERS),
        split=st.integers(min_value=1, max_value=159),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_reduce_conserves_mass_property(name, split, seed):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(160, 3)).astype(np.float32)
        w = rng.uniform(0.1, 2.0, size=(160,)).astype(np.float32)
        pol = SummarizerPolicy(name)
        s1 = summarize(x[:split], w[:split], jax.random.key(seed % 991),
                       k=3, t=4, policy=pol)
        s2 = summarize(x[split:], w[split:], jax.random.key(seed % 983),
                       k=3, t=4, policy=pol)
        red = reduce_summaries([s1, s2], jax.random.key(seed % 977),
                               k=3, t=4, policy=pol)
        np.testing.assert_allclose(float(red.weights.sum()), float(w.sum()),
                                   rtol=1e-3, atol=1e-4)


# ------------------------------------------------------- default bit-identity
def test_default_summarize_is_weighted_summary_outliers_bitwise():
    x = _data(seed=6)
    w = np.ones((x.shape[0],), np.float32)
    via_registry = summarize(x, w, jax.random.key(7), k=K, t=T)
    direct = weighted_summary_outliers(x, w, jax.random.key(7), k=K, t=T)
    np.testing.assert_array_equal(via_registry.points, direct.points)
    np.testing.assert_array_equal(via_registry.weights, direct.weights)
    np.testing.assert_array_equal(via_registry.is_candidate,
                                  direct.is_candidate)


def test_default_reduce_is_resummarize_bitwise():
    x = _data(seed=7)
    w = np.ones((x.shape[0],), np.float32)
    s1 = weighted_summary_outliers(x[:600], w[:600], jax.random.key(8),
                                   k=K, t=T)
    s2 = weighted_summary_outliers(x[600:], w[600:], jax.random.key(9),
                                   k=K, t=T)
    a = reduce_summaries([s1, s2], jax.random.key(10), k=K, t=T)
    b = resummarize([s1, s2], jax.random.key(10), k=K, t=T)
    np.testing.assert_array_equal(a.points, b.points)
    np.testing.assert_array_equal(a.weights, b.weights)


def test_tree_default_matches_explicit_paper_policy_bitwise():
    x = _data(n=3000, seed=8)
    roots = []
    for pol in (None, SummarizerPolicy("paper")):
        cfg = TreeConfig(dim=x.shape[1], k=K, t=T, leaf_size=512,
                         summarizer=pol, seed=1)
        tree = StreamTree(cfg)
        tree.ingest(x)
        roots.append(tree.root())
    np.testing.assert_array_equal(roots[0][0], roots[1][0])
    np.testing.assert_array_equal(roots[0][1], roots[1][1])


def test_distributed_cluster_default_matches_paper_policy_bitwise():
    x, _ = gauss(n_centers=6, per_center=200, t=40, sigma=0.1, seed=9)
    mesh = sites_mesh(1)
    res_default = distributed_cluster(jnp.asarray(x)[None],
                                      jax.random.key(0), mesh, k=6, t=40)
    res_policy = distributed_cluster(
        jnp.asarray(x)[None], jax.random.key(0), mesh, k=6, t=40,
        summarizer=summarizer_policy("paper", variant="augmented"))
    np.testing.assert_array_equal(np.asarray(res_default.centers),
                                  np.asarray(res_policy.centers))
    np.testing.assert_array_equal(np.asarray(res_default.outlier_ids),
                                  np.asarray(res_policy.outlier_ids))


# ------------------------------------------------------- per-site threading
def test_distributed_cluster_uniform_site_path():
    x, _ = gauss(n_centers=6, per_center=200, t=40, sigma=0.1, seed=10)
    mesh = sites_mesh(1)
    res = distributed_cluster(
        jnp.asarray(x)[None], jax.random.key(0), mesh, k=6, t=40,
        summarizer=summarizer_policy("uniform", budget=300))
    assert np.asarray(res.centers).shape == (6, x.shape[1])
    assert float(res.comm_records) <= 300


def test_distributed_cluster_host_only_summarizer_raises():
    x = _data(n=400, seed=11)
    mesh = sites_mesh(1)
    with pytest.raises(ValueError, match="no fixed-shape site path"):
        distributed_cluster(jnp.asarray(x)[None], jax.random.key(0), mesh,
                            k=K, t=T, summarizer=SummarizerPolicy("ball_cover"))


@pytest.mark.parametrize("name", ["ball_cover", "coreset"])
def test_simulate_coordinator_with_registry_summarizer(name):
    x, out_ids = gauss(n_centers=10, per_center=300, t=60, sigma=0.1, seed=12)
    parts, gids = partition(x, 4, "random", seed=0, outlier_ids=out_ids)
    res = simulate_coordinator(parts, jax.random.key(0), k=10, t=60,
                               summarizer=SummarizerPolicy(name))
    assert res["comm_records"] == len(res["summary_ids"])
    conc_w = float(np.sum(res["summary_weights"]))
    assert conc_w == pytest.approx(sum(p.shape[0] for p in parts), rel=1e-4)
    assert res["centers"].shape == (10, x.shape[1])


def test_ball_cover_beats_paper_center_count_under_heavy_noise():
    """t >> k heavy noise: aggregation folds noise balls into heavy ones,
    so ball_cover spends fewer records on scattered noise centers."""
    rng = np.random.default_rng(13)
    n, t = 4000, 400                       # 10% noise, k=4: t >> k
    x = np.concatenate([
        rng.normal(size=(n - t, 3)) * 0.05 +
        rng.choice([-4.0, 4.0], size=(n - t, 1)),
        rng.uniform(-40, 40, size=(t, 3)),
    ]).astype(np.float32)
    w = np.ones((n,), np.float32)
    s_paper = summarize(x, w, jax.random.key(1), k=4, t=t,
                        policy=SummarizerPolicy("paper"))
    s_bc = summarize(x, w, jax.random.key(1), k=4, t=t,
                     policy=SummarizerPolicy("ball_cover"))
    centers_paper = int((~s_paper.is_candidate).sum())
    centers_bc = int((~s_bc.is_candidate).sum())
    assert centers_bc < centers_paper
    np.testing.assert_allclose(float(s_bc.weights.sum()), n, rtol=1e-4)


def test_stream_service_accepts_summarizer_policy():
    x = _data(n=4000, seed=14)
    cfg = ServiceConfig(dim=x.shape[1], k=K, t=T, leaf_size=512,
                        refresh_every=2048,
                        summarizer=summarizer_policy("coreset", budget=256))
    svc = StreamService(cfg)
    svc.ingest(x)
    svc.refresh()
    assert svc.model is not None and int(svc.model.version) >= 1
    # coreset leaves are budget-bounded
    assert all(nd.summary.points.shape[0] <= 256 for nd in svc.tree.nodes)
    res = svc.score(x[:8])
    assert len(res) == 8


def test_sharded_service_threads_summarizer_to_site_trees():
    pol = summarizer_policy("uniform", budget=128)
    cfg = ShardedServiceConfig(dim=3, k=4, t=8, n_sites=3, leaf_size=256,
                               refresh_every=1024, summarizer=pol)
    svc = ShardedStreamService(cfg)
    assert all(tr.cfg.summarizer == pol for tr in svc.trees)
    svc.ingest(_data(n=2000, d=3, seed=15))
    svc.refresh()
    assert svc.model is not None


def test_process_default_summarizer_threading():
    x = _data(n=1500, seed=16)
    with using_summarizer(summarizer_policy("uniform", budget=96)):
        cfg = TreeConfig(dim=x.shape[1], k=K, t=T, leaf_size=512)
        tree = StreamTree(cfg)
        tree.ingest(x)
    assert cfg.summarizer.name == "uniform"
    assert all(nd.summary.points.shape[0] <= 96 for nd in tree.nodes)
    np.testing.assert_allclose(tree.total_weight, x.shape[0], rtol=1e-5)


# ----------------------------------------------------------- paper variants
def test_paper_variant_auto_rule():
    assert pick_augmented("auto", k=10, t=100, metric="l2sq")
    assert not pick_augmented("auto", k=10, t=5, metric="l2sq")
    assert not pick_augmented("auto", k=10, t=100, metric="cosine")
    assert pick_augmented("augmented", k=10, t=1, metric="l2sq")
    assert not pick_augmented("plain", k=10, t=100, metric="l2sq")
    with pytest.raises(ValueError, match="variant"):
        pick_augmented("bogus", k=10, t=1, metric="l2sq")


def test_site_summary_plain_is_summary_outliers_bitwise():
    from repro.core import summary_outliers

    x = jnp.asarray(_data(n=900, seed=17))
    via = site_summary(x, jax.random.key(3), k=K, t=T,
                       policy=summarizer_policy("paper", variant="plain"))
    direct = summary_outliers(x, jax.random.key(3), k=K, t=T)
    np.testing.assert_array_equal(np.asarray(via.points),
                                  np.asarray(direct.points))
    np.testing.assert_array_equal(np.asarray(via.weights),
                                  np.asarray(direct.weights))


def test_site_summary_host_only_raises():
    with pytest.raises(ValueError, match="no fixed-shape site path"):
        site_summary(jnp.zeros((64, 3)), jax.random.key(0), k=2, t=2,
                     policy=SummarizerPolicy("coreset"))


# ------------------------------------------------------------- cosine metric
def test_cosine_min_argmin_matches_manual():
    rng = np.random.default_rng(18)
    x = rng.normal(size=(500, 6)).astype(np.float32)
    c = rng.normal(size=(17, 6)).astype(np.float32)
    xn = x / np.linalg.norm(x, axis=1, keepdims=True)
    cn = c / np.linalg.norm(c, axis=1, keepdims=True)
    ref = 1.0 - xn @ cn.T
    d, a = (np.asarray(v) for v in min_argmin(jnp.asarray(x), jnp.asarray(c),
                                              metric="cosine"))
    np.testing.assert_allclose(d, ref.min(axis=1), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(a, ref.argmin(axis=1))


def test_cosine_never_auto_selects_pallas():
    from repro.kernels import dispatch
    from repro.kernels.dispatch import KernelPolicy

    reg = dispatch.select_backend("min_argmin", KernelPolicy(),
                                  metric="cosine", n=1000, m=32, d=8,
                                  platform="tpu")
    assert reg.name != "pallas"
    # naming pallas for cosine is an error, not a silent substitution
    with pytest.raises(ValueError, match="cannot serve"):
        dispatch.select_backend("min_argmin", KernelPolicy(backend="pallas"),
                                metric="cosine", n=1000, m=32, d=8,
                                platform="tpu")


def test_coreset_cosine_on_unit_normalized_susy():
    x, out_ids = susy_like(n=4000, t=60, seed=19)
    x = x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    w = np.ones((x.shape[0],), np.float32)
    summ = summarize(x, w, jax.random.key(4), k=10, t=60, metric="cosine",
                     policy=summarizer_policy("coreset", budget=512))
    _check_protocol(x, w, summ, 60)
    assert summ.points.shape[0] <= 512


def test_cosine_end_to_end_through_second_level():
    """metric='cosine' must survive the whole pipeline, not just the
    summarizer: the lloyd_step blocked/ref backends serve it (weighted-mean
    centers = the spherical k-means update), so simulate_coordinator and a
    cosine-configured stream refresh run to completion."""
    x, out_ids = susy_like(n=3000, t=50, seed=20)
    x = (x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
         ).astype(np.float32)
    parts, gids = partition(x, 3, "random", seed=0, outlier_ids=out_ids)
    res = simulate_coordinator(parts, jax.random.key(0), k=6, t=50,
                               metric="cosine",
                               summarizer=SummarizerPolicy("coreset"))
    assert res["centers"].shape == (6, x.shape[1])
    assert np.isfinite(res["cost"])

    cfg = ServiceConfig(dim=x.shape[1], k=6, t=50, leaf_size=512,
                        refresh_every=2048, metric="cosine")
    svc = StreamService(cfg)
    svc.ingest(x)
    svc.refresh()
    out = svc.score(x[:4])
    assert len(out) == 4 and all(np.isfinite(r.distance) for r in out)


def test_augmented_rejects_cosine():
    from repro.core import augmented_summary_outliers

    with pytest.raises(ValueError, match="cosine"):
        augmented_summary_outliers(jnp.zeros((64, 3)), jax.random.key(0),
                                   k=2, t=2, metric="cosine")
