"""PipelineConfig + Session facade: the one front door over every topology.

Contracts under test:
  * ``PipelineConfig`` serialization is exact — ``from_dict(to_dict(c))``
    and the full JSON round-trip reproduce an equal config (hypothesis
    fuzzes the valid space when installed), and invalid problem/topology
    combinations raise at construction, not at run time;
  * ``ServiceConfig`` / ``ShardedServiceConfig`` stay field-compatible
    through the shared ``BaseServiceConfig`` (no duplicated drifting
    fields);
  * ``Session`` adds no math: results are bit-identical to driving
    ``simulate_coordinator`` / ``distributed_cluster`` / ``StreamService``
    / ``ShardedStreamService`` directly with equivalent settings;
  * ``Session.save`` embeds the serialized config in the checkpoint
    manifest and ``Session.load`` reconstructs topology + policies from
    the checkpoint alone, with bit-identical post-restore scores, for all
    three topologies.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # optional: only the fuzz tests need hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.api import PipelineConfig, Session, pipeline_config
from repro.core import distributed_cluster, simulate_coordinator, sites_mesh
from repro.data.synthetic import gauss
from repro.kernels.dispatch import KernelPolicy
from repro.stream import (BaseServiceConfig, ServiceConfig,
                          ShardedServiceConfig, ShardedStreamService,
                          StreamService)
from repro.summarize import summarizer_policy


@pytest.fixture(scope="module")
def data():
    return gauss(n_centers=4, per_center=250, d=3, t=12, sigma=0.1, seed=0)


def _same_scores(a, b):
    assert len(a) == len(b)
    for p, r in zip(a, b):
        assert p.center == r.center
        assert p.distance == r.distance
        assert p.outlier_score == r.outlier_score


# ------------------------------------------------------------- serialization
def _configs():
    return [
        pipeline_config(dim=3, k=4, t=12),
        pipeline_config(dim=3, k=4, t=12, sites=5, partition="adversarial",
                        metric="l1", seed=9),
        pipeline_config(dim=5, k=2, t=0, topology="stream", leaf_size=128,
                        refresh_every=512, window=4096,
                        summarizer="uniform", kernels="blocked"),
        pipeline_config(dim=2, k=3, t=7, topology="sharded", sites=3,
                        site_budget="paper", async_refresh=True,
                        micro_batch=64,
                        summarizer=summarizer_policy("coreset", budget=64),
                        kernels=KernelPolicy(backend="ref", block_n=256)),
    ]


@pytest.mark.parametrize("idx", range(4))
def test_dict_and_json_round_trip_is_exact(idx):
    cfg = _configs()[idx]
    assert PipelineConfig.from_dict(cfg.to_dict()) == cfg
    # through real JSON text: tuples become lists, None becomes null —
    # from_dict must invert all of it
    assert PipelineConfig.from_json(cfg.to_json()) == cfg


def test_serialized_config_is_concrete():
    """A config never serializes a 'process default' placeholder: the
    policies captured at construction appear in the dict."""
    d = pipeline_config(dim=3, k=4, t=12).to_dict()
    assert d["summarizer"]["name"] == "auto"
    assert d["kernels"]["backend"] == "auto"
    assert set(d) == {"version", "problem", "topology", "summarizer",
                      "kernels", "second_iters", "seed"}


def test_from_dict_rejects_unknown_and_missing_keys():
    good = pipeline_config(dim=3, k=4, t=12).to_dict()
    with pytest.raises(ValueError, match="unknown config keys"):
        PipelineConfig.from_dict({**good, "extra": 1})
    with pytest.raises(ValueError, match="unknown topology keys"):
        PipelineConfig.from_dict(
            {**good, "topology": {**good["topology"], "n_sites": 2}})
    with pytest.raises(ValueError, match="missing"):
        PipelineConfig.from_dict({k: v for k, v in good.items()
                                  if k != "problem"})
    with pytest.raises(ValueError, match="version"):
        PipelineConfig.from_dict({**good, "version": 99})


@pytest.mark.parametrize("bad", [
    dict(dim=0, k=4, t=10),
    dict(dim=3, k=0, t=10),
    dict(dim=3, k=4, t=-1),
    dict(dim=3, k=4, t=10, metric="chebyshev"),
    dict(dim=3, k=4, t=10, topology="ring"),
    dict(dim=3, k=4, t=10, topology="stream", sites=3),
    dict(dim=3, k=4, t=10, window=100),                       # oneshot window
    dict(dim=3, k=4, t=10, async_refresh=True),               # oneshot async
    dict(dim=3, k=4, t=10, refresh_every=4096),               # oneshot cadence
    dict(dim=3, k=4, t=10, leaf_size=512),                    # oneshot leaf
    dict(dim=3, k=4, t=10, topology="stream", partition="adversarial"),
    dict(dim=3, k=4, t=10, topology="stream", site_budget="paper"),
    dict(dim=3, k=4, t=10, topology="stream", use_shard_map=True),
    dict(dim=3, k=4, t=10, topology="sharded", sites=0),
    dict(dim=3, k=4, t=10, topology="stream", window=0),
    dict(dim=3, k=4, t=10, summarizer="nope"),
    dict(dim=3, k=4, t=10, use_shard_map=True,
         summarizer="ball_cover"),                            # host-driven
    dict(dim=3, k=4, t=10,                                    # KernelPolicy
         kernels={"backend": "auto", "block_n": 0}),          # rejects it
])
def test_invalid_configs_raise_at_construction(bad):
    with pytest.raises(ValueError):
        pipeline_config(**bad)


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_fuzzed_valid_configs_round_trip():
    kinds = st.sampled_from(["oneshot", "stream", "sharded"])

    @st.composite
    def configs(draw):
        kind = draw(kinds)
        topo = {"kind": kind}
        if kind == "sharded":
            topo["sites"] = draw(st.integers(1, 8))
            topo["site_budget"] = draw(st.sampled_from(["full", "paper"]))
        if kind == "oneshot":
            topo["sites"] = draw(st.integers(1, 8))
            topo["partition"] = draw(
                st.sampled_from(["random", "adversarial"]))
        else:
            topo["refresh_every"] = draw(st.integers(1, 1 << 20))
            topo["leaf_size"] = draw(st.integers(1, 1 << 16))
            topo["window"] = draw(
                st.one_of(st.none(), st.integers(1, 1 << 20)))
            topo["async_refresh"] = draw(st.booleans())
        topo["micro_batch"] = draw(st.integers(1, 4096))
        return pipeline_config(
            dim=draw(st.integers(1, 64)),
            k=draw(st.integers(1, 32)),
            t=draw(st.integers(0, 1000)),
            metric=draw(st.sampled_from(["l2sq", "l2", "l1", "cosine"])),
            topology=kind,
            summarizer=draw(st.sampled_from(
                ["auto", "paper", "uniform", "ball_cover", "coreset"])),
            kernels=KernelPolicy(
                backend=draw(st.sampled_from(
                    ["auto", "pallas", "blocked", "ref"])),
                block_n=draw(st.one_of(st.none(),
                                       st.integers(1, 1 << 20))),
                autotune=draw(st.booleans())),
            second_iters=draw(st.integers(1, 100)),
            seed=draw(st.integers(-2**31, 2**31 - 1)),
            **topo)

    @settings(max_examples=60, deadline=None)
    @given(cfg=configs())
    def run(cfg):
        assert PipelineConfig.from_dict(cfg.to_dict()) == cfg
        assert PipelineConfig.from_json(
            json.dumps(json.loads(cfg.to_json()))) == cfg

    run()


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_fuzzed_invalid_scalars_raise():
    @settings(max_examples=40, deadline=None)
    @given(dim=st.integers(-5, 0), k=st.integers(-5, 0),
           t=st.integers(-5, -1))
    def run(dim, k, t):
        for bad in (dict(dim=dim, k=4, t=10), dict(dim=3, k=k, t=10),
                    dict(dim=3, k=4, t=t)):
            with pytest.raises(ValueError):
                pipeline_config(**bad)

    run()


# --------------------------------------------------------- config field dedup
def test_stream_configs_stay_field_compatible_through_base():
    """The sharded config is the base config plus topology-only fields —
    asserting here means a field added to one serving config cannot
    silently drift from the other again."""
    base = {f.name: f for f in dataclasses.fields(BaseServiceConfig)}
    single = {f.name: f for f in dataclasses.fields(ServiceConfig)}
    sharded = {f.name: f for f in dataclasses.fields(ShardedServiceConfig)}
    assert issubclass(ServiceConfig, BaseServiceConfig)
    assert issubclass(ShardedServiceConfig, BaseServiceConfig)
    # the single-host config is exactly the shared base ...
    assert set(single) == set(base)
    # ... and every shared field agrees in type and default on both sides
    assert set(base) <= set(sharded)
    for name, f in base.items():
        for other in (single[name], sharded[name]):
            assert other.type == f.type, name
            assert other.default == f.default or (
                f.default is dataclasses.MISSING
                and other.default is dataclasses.MISSING), name
    # sharded extras are topology-only
    assert set(sharded) - set(base) == {"n_sites", "site_budget",
                                        "use_shard_map"}


def test_pipeline_projects_onto_stream_configs():
    cfg = pipeline_config(dim=3, k=4, t=12, topology="sharded", sites=3,
                          leaf_size=256, refresh_every=1024, window=8192,
                          site_budget="paper", seed=7)
    sc = cfg.sharded_config()
    assert (sc.dim, sc.k, sc.t, sc.n_sites) == (3, 4, 12, 3)
    assert (sc.leaf_size, sc.refresh_every, sc.window) == (256, 1024, 8192)
    assert sc.site_budget == "paper" and sc.seed == 7
    assert sc.policy == cfg.kernels and sc.summarizer == cfg.summarizer
    with pytest.raises(ValueError, match="stream"):
        cfg.service_config()


# ------------------------------------------------------- session bit-identity
def test_oneshot_session_matches_simulate_coordinator(data):
    x, _ = data
    cfg = pipeline_config(dim=3, k=4, t=12, sites=3, seed=5)
    sess = Session(cfg)
    sess.fit(x)
    direct = simulate_coordinator(
        np.array_split(x, 3), jax.random.key(5), k=4, t=12,
        summarizer=cfg.summarizer, policy=cfg.kernels)
    assert (sess.result["centers"] == direct["centers"]).all()
    assert (sess.result["outlier_ids"] == direct["outlier_ids"]).all()
    assert (sess.result["summary_ids"] == direct["summary_ids"]).all()
    assert sess.result["cost"] == direct["cost"]
    assert sess.result["comm_records"] == direct["comm_records"]


def test_oneshot_session_matches_distributed_cluster_shard_map(data):
    x, _ = data
    cfg = pipeline_config(dim=3, k=4, t=12, sites=1, use_shard_map=True,
                          seed=5)
    sess = Session(cfg)
    sess.fit(x)
    res = distributed_cluster(
        jnp.asarray(x)[None], jax.random.key(5),
        sites_mesh(1), k=4, t=12,
        summarizer=cfg.summarizer, policy=cfg.kernels)
    assert (sess.result["centers"] == np.asarray(res.centers)).all()
    out = np.asarray(res.outlier_ids)
    assert (sess.result["outlier_ids"] == out[out >= 0]).all()
    assert sess.result["cost"] == float(res.cost)


# On a real multi-device mesh the oneshot Session's use_shard_map path
# must still add no math of its own: same key, same mesh => results equal
# driving distributed_cluster directly, bit for bit — and a save/load of
# that session must re-score bitwise.  Mirrors _SHARD_MAP_EQ in
# tests/test_stream_sharded.py: forced 4-device CPU in a subprocess
# because XLA_FLAGS must be set before jax initializes.
_ONESHOT_SHARD_MAP_EQ = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_DEFAULT_PRNG_IMPL"] = "threefry2x32"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.api import Session, pipeline_config
    from repro.core import distributed_cluster, sites_mesh
    from repro.data.synthetic import gauss

    x, _ = gauss(n_centers=4, per_center=500, d=3, t=16, sigma=0.05,
                 seed=11)
    x = x[: (len(x) // 4) * 4].astype(np.float32)
    cfg = pipeline_config(dim=3, k=4, t=16, sites=4, use_shard_map=True,
                          seed=11)
    sess = Session(cfg)
    sess.fit(x)
    res = distributed_cluster(
        jnp.asarray(x).reshape(4, -1, 3), jax.random.key(11),
        sites_mesh(4), k=4, t=16, summarizer=cfg.summarizer,
        policy=cfg.kernels)
    out = np.asarray(res.outlier_ids)
    q = x[:64]
    before = sess.score(q)
    with tempfile.TemporaryDirectory() as ckpt:
        sess.save(ckpt)
        after = Session.load(ckpt).score(q)
    print(json.dumps({
        "n_devices": len(jax.devices()),
        "centers_equal": bool(np.array_equal(
            sess.result["centers"], np.asarray(res.centers))),
        "cost_equal": sess.result["cost"] == float(res.cost),
        "outliers_equal": bool(np.array_equal(
            sess.result["outlier_ids"], out[out >= 0])),
        "reload_scores_equal": all(
            a.center == b.center and a.distance == b.distance
            and a.outlier_score == b.outlier_score
            for a, b in zip(before, after)),
    }))
""")


@pytest.mark.slow
def test_oneshot_shard_map_session_bit_identical_multi_device_subprocess():
    """Real 4-device shard_map oneshot Session == direct
    distributed_cluster on the same mesh, bitwise (plus save/load)."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _ONESHOT_SHARD_MAP_EQ],
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n_devices"] == 4
    assert res["centers_equal"] and res["cost_equal"]
    assert res["outliers_equal"] and res["reload_scores_equal"]


def test_stream_session_matches_stream_service(data):
    x, _ = data
    q = x[:9]
    cfg = pipeline_config(dim=3, k=4, t=12, topology="stream",
                          leaf_size=256, refresh_every=512)
    sess = Session(cfg)
    sess.ingest(x)
    sess.refresh()
    svc = StreamService(ServiceConfig(dim=3, k=4, t=12, leaf_size=256,
                                      refresh_every=512))
    svc.ingest(x)
    svc.refresh()
    assert int(sess.model.version) == int(svc.model.version)
    _same_scores(sess.score(q), svc.score(q))


def test_sharded_session_matches_sharded_service(data):
    x, _ = data
    q = x[:9]
    cfg = pipeline_config(dim=3, k=4, t=12, topology="sharded", sites=3,
                          leaf_size=256, refresh_every=512)
    sess = Session(cfg)
    sess.ingest(x)
    sess.refresh()
    svc = ShardedStreamService(ShardedServiceConfig(
        dim=3, k=4, t=12, n_sites=3, leaf_size=256, refresh_every=512))
    svc.ingest(x)
    svc.refresh()
    _same_scores(sess.score(q), svc.score(q))
    # comm accounting surfaces through the engine escape hatch
    assert sess.engine.last_refresh.comm_records == \
        svc.last_refresh.comm_records


def test_config_json_round_trip_preserves_behavior(data):
    """to_dict -> JSON -> from_dict -> Session behaves identically."""
    x, _ = data
    q = x[:9]
    cfg = pipeline_config(dim=3, k=4, t=12, topology="stream",
                          leaf_size=256, refresh_every=512, seed=3)
    rt = PipelineConfig.from_json(cfg.to_json())
    assert rt == cfg
    a, b = Session(cfg), Session(rt)
    for s in (a, b):
        s.ingest(x)
        s.refresh()
    _same_scores(a.score(q), b.score(q))


def test_oneshot_refresh_is_pure(data):
    """Refreshing with no new data reproduces the model bit for bit (the
    oneshot fit is a function of the ingested points and the seed)."""
    x, _ = data
    sess = Session(pipeline_config(dim=3, k=4, t=12, sites=2))
    m1 = sess.fit(x)
    m2 = sess.refresh()
    assert (np.asarray(m1.centers) == np.asarray(m2.centers)).all()
    assert float(m1.threshold) == float(m2.threshold)
    assert int(m2.version) == int(m1.version) + 1


# ------------------------------------------------------------ session errors
def test_session_error_surface(data):
    x, _ = data
    sess = Session(pipeline_config(dim=3, k=4, t=12))
    with pytest.raises(RuntimeError, match="refresh"):
        sess.score(x[:2])
    with pytest.raises(ValueError, match="unit-weight"):
        sess.ingest(x[:4], np.ones(4))
    with pytest.raises(ValueError, match="sharded"):
        sess.ingest(x[:4], site=0)
    with pytest.raises(ValueError, match="(n, 3)"):
        sess.ingest(x[:4, :2])
    sharded = Session(pipeline_config(dim=3, k=4, t=12, topology="sharded",
                                      sites=2))
    sharded.ingest(x[:4], site=1)   # pinned routing reaches site 1
    assert sharded.engine.trees[1].total_ingested == 4


# ------------------------------------------------------------- save / load
@pytest.mark.parametrize("kind", ["oneshot", "stream", "sharded"])
def test_save_load_score_bit_identical(tmp_path, data, kind):
    x, _ = data
    q = x[:9]
    kw = dict(dim=3, k=4, t=12, topology=kind)
    if kind != "oneshot":
        kw.update(leaf_size=256, refresh_every=512)
    if kind == "sharded":
        kw.update(sites=3)
    cfg = pipeline_config(**kw)
    sess = Session(cfg)
    sess.fit(x)
    before = sess.score(q)
    step = sess.save(tmp_path)
    restored = Session.load(tmp_path, step=step)
    # topology + policies came from the manifest alone
    assert restored.config == cfg
    _same_scores(before, restored.score(q))
    if kind == "oneshot":
        # the coordinator detail survives the round trip too
        for key in ("centers", "outlier_ids", "summary_ids",
                    "summary_weights"):
            assert (restored.result[key] == sess.result[key]).all(), key
        assert restored.result["cost"] == sess.result["cost"]
        assert restored.result["comm_records"] == \
            sess.result["comm_records"]
    # the restored session keeps working: ingest more, refresh, score
    restored.ingest(x[:64])
    restored.refresh()
    assert int(restored.model.version) == int(sess.model.version) + 1


def test_load_refuses_checkpoint_without_embedded_config(tmp_path, data):
    x, _ = data
    svc = StreamService(ServiceConfig(dim=3, k=4, t=12, leaf_size=256))
    svc.ingest(x[:512])
    svc.refresh()
    from repro.checkpoint.manager import CheckpointManager
    svc.save(CheckpointManager(tmp_path), step=1)
    with pytest.raises(ValueError, match="embedded pipeline config"):
        Session.load(tmp_path)


def test_save_load_weighted_ingest_stream(tmp_path, data):
    """Weighted records survive the facade round trip (stream topology)."""
    x, _ = data
    cfg = pipeline_config(dim=3, k=4, t=12, topology="stream",
                          leaf_size=256, refresh_every=512)
    sess = Session(cfg)
    sess.ingest(x[:600], np.full(600, 2.0, np.float32))
    sess.refresh()
    assert sess.engine.tree.total_weight == pytest.approx(1200.0)
    sess.save(tmp_path)
    assert Session.load(tmp_path).engine.tree.total_weight == \
        pytest.approx(1200.0)


# ------------------------------------------------------------------ CLI
def test_cli_run_and_load_round_trip(tmp_path, data, capsys):
    from repro.api.cli import main as cli_main

    artifact = {
        "pipeline": pipeline_config(dim=3, k=4, t=12, sites=2).to_dict(),
        "data": {"kind": "gauss", "n_centers": 4, "per_center": 250,
                 "d": 3, "t": 12, "sigma": 0.1, "seed": 0},
    }
    cfg_path = tmp_path / "run.json"
    cfg_path.write_text(json.dumps(artifact))
    save_dir = tmp_path / "ckpt"
    cli_main(["run", "--config", str(cfg_path), "--queries", "16",
              "--save", str(save_dir)])
    out = capsys.readouterr().out
    assert "ok" in out and "outliers:" in out
    restored = Session.load(save_dir)
    assert restored.config.topology.sites == 2
    assert restored.model is not None


def test_cli_rejects_bad_artifacts(tmp_path):
    from repro.api.cli import main as cli_main

    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"nope": 1}))
    with pytest.raises(SystemExit, match="pipeline"):
        cli_main(["run", "--config", str(p)])
    p.write_text(json.dumps({
        "pipeline": pipeline_config(dim=4, k=3, t=5).to_dict(),
        "data": {"kind": "gauss", "d": 3, "n_centers": 3, "per_center": 50,
                 "t": 5},
    }))
    with pytest.raises(SystemExit, match="dim"):
        cli_main(["run", "--config", str(p)])
