"""The telemetry plane: registry semantics, layer instrumentation,
front-door snapshot coverage, and the bit-identity guarantee.

Most tests isolate themselves with ``obs.using_registry`` so process-wide
series from other tests don't leak in; the layer tests construct their
services *inside* the scope because instrumented layers capture metric
handles at construction.
"""
from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.api.config import pipeline_config
from repro.api.session import Session
from repro.data.synthetic import gauss
from repro.obs.registry import metric_key, split_key
from repro.stream.service import ServiceConfig, StreamService
from repro.stream.sharded import ShardedServiceConfig, ShardedStreamService


# --------------------------------------------------------------- registry
def test_histogram_percentiles_match_numpy():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat")
    rng = np.random.default_rng(0)
    xs = rng.exponential(scale=0.01, size=1500)
    for v in xs:
        h.observe(v)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)
    e = h.snapshot_entry()
    assert e["p50"] == pytest.approx(float(np.percentile(xs, 50)))
    assert e["p95"] == pytest.approx(float(np.percentile(xs, 95)))
    assert e["p99"] == pytest.approx(float(np.percentile(xs, 99)))
    assert e["count"] == 1500
    assert e["min"] == pytest.approx(xs.min())
    assert e["max"] == pytest.approx(xs.max())


def test_histogram_ring_bounds_memory_but_buckets_stay_cumulative():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat", ring=100)
    for v in np.linspace(0.001, 0.002, 1000):
        h.observe(v)
    e = h.snapshot_entry()
    assert e["count"] == 1000                    # buckets: full history
    assert e["buckets"]["+Inf"] == 1000
    assert len(h._ring) == 100                   # ring: bounded
    # percentiles computed over the *recent* 100 samples
    recent = np.linspace(0.001, 0.002, 1000)[-100:]
    assert h.percentile(50) == pytest.approx(float(np.percentile(recent, 50)))


def test_histogram_bucket_le_semantics():
    reg = obs.MetricsRegistry()
    h = reg.histogram("x", buckets=(1.0, 2.0))
    for v in (0.5, 1.0, 1.5, 2.0, 99.0):
        h.observe(v)
    b = h.snapshot_entry()["buckets"]
    assert b["1"] == 2        # 0.5, 1.0  (le-inclusive)
    assert b["2"] == 4        # + 1.5, 2.0
    assert b["+Inf"] == 5


def test_snapshot_golden_schema():
    """The snapshot dict is a cross-PR surface — shape pinned here."""
    reg = obs.MetricsRegistry()
    reg.counter("c", a="1").inc(3)
    reg.gauge("g").set(2.5)
    reg.histogram("h", buckets=(0.1,)).observe(0.05)
    snap = reg.snapshot()
    assert snap == {
        "version": 2,
        "enabled": True,
        "counters": {"c{a=1}": 3},
        "gauges": {"g": 2.5},
        "histograms": {"h": {
            "count": 1, "sum": 0.05, "min": 0.05, "max": 0.05,
            "p50": 0.05, "p95": 0.05, "p99": pytest.approx(0.05),
            "buckets": {"0.1": 1, "+Inf": 1},
        }},
        "alerts": [],
        "trace": {
            "enabled": True, "sample_rate": 1.0, "ring": 65536,
            "recorded": 0, "buffered": 0, "dropped": 0, "traces": 0,
        },
    }
    json.dumps(snap)   # JSON-serializable as-is


def test_counter_thread_safety():
    reg = obs.MetricsRegistry()
    c = reg.counter("hits")
    n_threads, per = 8, 5000

    def work():
        for _ in range(per):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per


def test_histogram_thread_safety():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat")
    n_threads, per = 4, 2000

    def work():
        for _ in range(per):
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    e = h.snapshot_entry()
    assert e["count"] == n_threads * per
    assert e["buckets"]["+Inf"] == n_threads * per


def test_disabled_registry_is_noop():
    reg = obs.MetricsRegistry(enabled=False)
    reg.counter("c").inc()
    reg.gauge("g").set(1.0)
    reg.histogram("h").observe(1.0)
    with reg.trace("p"):
        pass
    snap = reg.snapshot()
    assert snap["enabled"] is False
    assert snap["counters"]["c"] == 0
    assert snap["histograms"]["h"]["count"] == 0
    assert "phase.p" not in snap["histograms"]


def test_gauge_callable_and_failure():
    reg = obs.MetricsRegistry()
    reg.gauge("ok").set_fn(lambda: 42)
    reg.gauge("bad").set_fn(lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["gauges"]["ok"] == 42.0
    assert snap["gauges"]["bad"] is None   # failing gauge never raises


def test_metric_key_roundtrip_and_sanitization():
    key = metric_key("comm.records", {"site": 3, "topology": "sharded"})
    assert key == "comm.records{site=3,topology=sharded}"
    assert split_key(key) == ("comm.records",
                              {"site": "3", "topology": "sharded"})
    assert split_key("plain") == ("plain", {})
    # label values that would break the key format are sanitized
    assert "{" not in metric_key("m", {"v": "a{b}=c,d"}).split("{", 1)[1][:-1]\
        .split("=", 1)[1]


def test_trace_span_records_wall_time():
    reg = obs.MetricsRegistry()
    with reg.trace("fit", topology="t"):
        pass
    e = reg.snapshot()["histograms"]["phase.fit{topology=t}"]
    assert e["count"] == 1 and e["sum"] >= 0


def test_trace_without_profiler_records_histogram_as_before():
    from jax.profiler import TraceAnnotation
    from repro.obs.tracing import _NULL_SPAN
    assert not TraceAnnotation.is_enabled()
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        for _ in range(3):
            with obs.trace("step", topology="t"):
                pass
        e = reg.snapshot()["histograms"]["phase.step{topology=t}"]
        assert e["count"] == 3 and e["sum"] >= 0
        assert reg.recorder.spans() == []   # no trace: no flight record
    # metrics off, no trace and no profiler: the span does nothing at all
    assert obs.MetricsRegistry(enabled=False).trace("p") is _NULL_SPAN


def test_gc_collect_adds_one_gen2_observation():
    import gc
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        def gen2():
            snap = reg.snapshot()
            return (snap["histograms"].get("phase.runtime.gc{gen=2}",
                                           {"count": 0})["count"],
                    snap["counters"].get("runtime.gc.collections{gen=2}", 0))
        before = gen2()
        gc.collect()
        after = gen2()
    assert after == (before[0] + 1, before[1] + 1)
    e = reg.snapshot()["histograms"]["phase.runtime.gc{gen=2}"]
    assert e["sum"] > 0


def test_gc_hook_completes_a_collection_started_inside_observe():
    """A collection that starts while ``Histogram.observe`` holds its lock
    runs the hook to the end: the hook takes no registry lock."""
    import gc

    class CollectingRing(list):
        def append(self, v):
            super().append(v)
            if len(self) == 1:
                gc.collect()

    with obs.using_registry(obs.MetricsRegistry()) as reg:
        reg.snapshot()
        h = reg.histogram("phase.runtime.gc", gen=2)
        h._ring = CollectingRing()
        done = []

        def observe():
            with reg._lock:          # the registry's lock is held too
                h.observe(0.5)
            done.append(True)

        th = threading.Thread(target=observe, daemon=True)
        th.start()
        th.join(timeout=60.0)
        assert not th.is_alive() and done
        e = reg.snapshot()["histograms"]["phase.runtime.gc{gen=2}"]
        assert e["count"] == 2      # the 0.5 observed, then the collection
        assert reg.snapshot()["counters"][
            "runtime.gc.collections{gen=2}"] >= 1


def test_summary_flush_counts_rounds_and_device_bytes():
    from repro.stream.tree import StreamTree, TreeConfig
    from repro.stream.weighted import max_rounds
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        tree = StreamTree(TreeConfig(dim=4, k=3, t=5, leaf_size=512))
        tree.ingest(_ingest_data(n=512))
        c = reg.snapshot()["counters"]
    assert max_rounds(512, 5, 0.45) >= 1
    assert sum(v for k, v in c.items()
               if k.startswith("tree.leaf_flushes")) == 1
    assert c["summary.rounds"] >= 1
    # each round sends at least its padded rows (a 256-row bucket of d=4
    # float32) to the device
    assert c["summary.h2d_bytes"] >= c["summary.rounds"] * 256 * 4 * 4


def test_using_registry_scopes_default():
    base = obs.get_default_registry()
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        assert obs.get_default_registry() is reg
        obs.counter("scoped").inc()
        assert reg.snapshot()["counters"]["scoped"] == 1
    assert obs.get_default_registry() is base
    assert "scoped" not in base.snapshot()["counters"]


def test_prometheus_rendering():
    reg = obs.MetricsRegistry()
    reg.counter("comm.records", site=0).inc(7)
    reg.gauge("tree.records").set(12)
    reg.histogram("serve.latency", buckets=(0.01,),
                  topology="stream").observe(0.005)
    txt = obs.render_prometheus(reg.snapshot())
    assert "# TYPE comm_records_total counter" in txt
    assert 'comm_records_total{site="0"} 7' in txt
    assert "tree_records 12" in txt
    assert "# TYPE serve_latency histogram" in txt
    assert 'serve_latency_bucket{le="0.01",topology="stream"} 1' in txt
    assert 'serve_latency_count{topology="stream"} 1' in txt
    assert ('serve_latency_quantile{quantile="0.5",topology="stream"}'
            in txt)


# ------------------------------------------------------------ layer wiring
def _stream_cfg(**kw):
    base = dict(dim=4, k=3, t=8, leaf_size=64, refresh_every=256,
                micro_batch=32, second_iters=5, seed=0)
    base.update(kw)
    return ServiceConfig(**base)


def _ingest_data(n=600, d=4, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


def test_latency_stats_compat_shim():
    with obs.using_registry(obs.MetricsRegistry()):
        svc = StreamService(_stream_cfg())
        empty = svc.latency_stats()
        assert empty["count"] == 0
        assert np.isnan(empty["p50_ms"]) and np.isnan(empty["p99_ms"])
        svc.ingest(_ingest_data())
        svc.refresh()
        svc.score(_ingest_data(70))
        stats = svc.latency_stats()
        assert set(stats) == {"count", "p50_ms", "p99_ms"}
        assert stats["count"] == 70
        assert np.isfinite(stats["p50_ms"])
        assert stats["p50_ms"] <= stats["p99_ms"]
        svc.reset_latency_stats()
        assert svc.latency_stats()["count"] == 0


def test_bounded_latency_state():
    """The unbounded-list leak is gone: latency state is O(ring), not O(n)."""
    with obs.using_registry(obs.MetricsRegistry()):
        svc = StreamService(_stream_cfg())
        assert not hasattr(svc, "_latencies")
        assert svc._lat._ring.maxlen == obs.DEFAULT_RING


def test_single_host_refresh_stats_and_staleness():
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        svc = StreamService(_stream_cfg())
        assert svc.last_fit is None
        assert svc.seconds_since_install() is None
        svc.ingest(_ingest_data())
        svc.refresh()
        assert svc.last_fit is not None
        assert svc.last_fit.version == int(svc.model.version)
        assert svc.last_fit.records_folded > 0
        assert svc.last_fit.fit_s >= 0
        age = svc.seconds_since_install()
        assert age is not None and age >= 0
        snap = reg.snapshot()
        g = snap["gauges"]["model.seconds_since_install{topology=stream}"]
        assert g is not None and g >= age   # gauge evaluates later => older
        assert snap["histograms"][
            "phase.refresh.fit{topology=stream}"]["count"] >= 1


def test_async_refresh_stats_install_at_poll():
    with obs.using_registry(obs.MetricsRegistry()):
        svc = StreamService(_stream_cfg(async_refresh=True))
        svc.ingest(_ingest_data(200))
        svc.refresh(blocking=False)
        svc.join_refresh()
        assert svc.last_fit is not None
        assert svc.last_fit.version == int(svc.model.version)
        assert svc.last_fit.records_folded > 0


def test_stream_snapshot_covers_tree_and_phases():
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        svc = StreamService(_stream_cfg())
        svc.ingest(_ingest_data())
        svc.refresh()
        svc.score(_ingest_data(40))
        snap = reg.snapshot()
        c, h, g = snap["counters"], snap["histograms"], snap["gauges"]
        summ = svc.cfg.summarizer.name
        assert c[f"tree.leaf_flushes{{summarizer={summ}}}"] >= 2
        assert c["ingest.points{topology=stream}"] == 600
        assert c["score.requests{topology=stream}"] == 40
        assert h[f"phase.ingest.leaf_flush{{summarizer={summ}}}"][
            "count"] >= 2
        assert h["phase.score.fused{topology=stream}"]["count"] >= 1
        assert g[f"tree.records{{summarizer={summ}}}"] > 0
        assert any(k.startswith("kernels.dispatch{") for k in c)


def test_sharded_comm_accounting_matches_refresh_stats():
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        cfg = ShardedServiceConfig(
            dim=4, k=3, t=8, n_sites=3, leaf_size=64, refresh_every=256,
            micro_batch=32, second_iters=5, seed=0)
        svc = ShardedStreamService(cfg)
        svc.ingest(_ingest_data(600))
        svc.refresh()
        st = svc.last_refresh
        snap = reg.snapshot()
        c = snap["counters"]
        assert c["comm.rounds{topology=sharded}"] == int(st.version)
        # the LAST refresh's per-site records are the final increments;
        # totals accumulate over all refreshes, so each site's counter is
        # at least its last contribution
        for i, rec in enumerate(st.per_site_records):
            key = f"comm.records{{site={i},topology=sharded}}"
            assert c[key] >= rec
        key0 = "comm.bytes{site=0,topology=sharded}"
        assert c[key0] >= st.payload_bytes
        # per-site tree series carry the site label
        summ = svc.trees[0].cfg.summarizer.name
        assert f"tree.records{{site=0,summarizer={summ}}}" in snap["gauges"]


def _site_counts(counters: dict, name: str) -> dict:
    """{site: value} of a per-site counter (one label set per site)."""
    out = {}
    for key, v in counters.items():
        base, labels = split_key(key)
        if base == name:
            out[int(labels["site"])] = v
    return out


def test_comm_rows_is_the_padded_root_of_every_refresh():
    """``comm.rows{site}`` grows by the packed-root rows each site puts on
    the exchange, refresh by refresh, and never counts fewer than the
    live records beside it."""
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        cfg = ShardedServiceConfig(
            dim=4, k=3, t=8, n_sites=3, leaf_size=1024, refresh_every=10**6,
            micro_batch=32, second_iters=5, seed=0)
        svc = ShardedStreamService(cfg)
        data = _ingest_data(900)
        seen_rows = set()
        before = reg.snapshot()["counters"]
        for lo in range(0, 900, 150):
            svc.ingest(data[lo:lo + 150])
            svc.refresh()
            st = svc.last_refresh
            after = reg.snapshot()["counters"]
            rows1, rows0 = (_site_counts(after, "comm.rows"),
                            _site_counts(before, "comm.rows"))
            rec1, rec0 = (_site_counts(after, "comm.records"),
                          _site_counts(before, "comm.records"))
            for i in range(cfg.n_sites):
                assert rows1[i] - rows0.get(i, 0) == st.root_rows
                assert rec1[i] - rec0.get(i, 0) == st.per_site_records[i]
                assert rec1[i] <= rows1[i]
            seen_rows.add(st.root_rows)
            before = after
        assert len(seen_rows) >= 2        # the bucket grew along the way


def test_comm_rows_on_the_one_shot_paths():
    """The host-simulated coordinator sends exactly its live records; the
    shard_map one-shot puts each site's whole fixed-shape summary on the
    exchange."""
    import jax
    import jax.numpy as jnp

    from repro.core.collective import sites_mesh
    from repro.core.distributed import (distributed_cluster,
                                        simulate_coordinator)
    x, _ = gauss(n_centers=3, per_center=150, d=4, t=10, seed=0)
    x = np.asarray(x, np.float32)
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        simulate_coordinator([x[:225], x[225:]], jax.random.key(0), k=3,
                             t=10, second_iters=5)
        c = reg.snapshot()["counters"]
        rows = _site_counts(c, "comm.rows")
        assert rows == _site_counts(c, "comm.records") and len(rows) == 2
        assert all(v > 0 for v in rows.values())
    with obs.using_registry(obs.MetricsRegistry()) as reg:
        res = distributed_cluster(jnp.asarray(x)[None], jax.random.key(0),
                                  sites_mesh(1), k=3, t=10, second_iters=5)
        c = reg.snapshot()["counters"]
        cap = np.asarray(res.summary_ids).size
        assert _site_counts(c, "comm.rows") == {0: cap}
        assert _site_counts(c, "comm.records")[0] <= cap


def test_scores_bit_identical_with_metrics_on_and_off():
    x = _ingest_data(600)
    q = _ingest_data(64, seed=7)

    def run() -> list:
        svc = StreamService(_stream_cfg())
        svc.ingest(x)
        svc.refresh()
        return svc.score(q)

    with obs.using_registry(obs.MetricsRegistry(enabled=True)):
        res_on = run()
    with obs.using_registry(obs.MetricsRegistry(enabled=False)):
        res_off = run()
    for a, b in zip(res_on, res_off):
        assert a.request_id == b.request_id
        assert a.center == b.center
        assert a.distance == b.distance            # bit-identical
        assert a.outlier_score == b.outlier_score
        assert a.is_outlier == b.is_outlier


def test_checkpoint_metrics(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    with obs.using_registry(obs.MetricsRegistry()) as reg:
        mgr = CheckpointManager(tmp_path)
        state = {"a": np.arange(100, dtype=np.float32)}
        mgr.save(1, state, blocking=True)
        restored, _ = mgr.restore({"a": np.zeros(100, np.float32)})
        snap = reg.snapshot()
        c = snap["counters"]
        assert c["checkpoint.saves"] == 1
        assert c["checkpoint.restores"] == 1
        assert c["checkpoint.bytes_written"] == 400
        assert c["checkpoint.bytes_read"] == 400
        assert snap["histograms"]["phase.checkpoint.save"]["count"] == 1
        assert snap["histograms"]["phase.checkpoint.restore"]["count"] == 1
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      state["a"])


# --------------------------------------------------------------- front door
def _session_snapshot(kind: str) -> dict:
    topo_kw = {}
    if kind in ("stream", "sharded"):
        topo_kw = dict(leaf_size=64, refresh_every=256, micro_batch=32)
    if kind == "sharded":
        topo_kw["sites"] = 2
    cfg = pipeline_config(dim=4, k=3, t=10, topology=kind,
                          second_iters=5, seed=0, **topo_kw)
    x, _ = gauss(n_centers=3, per_center=150, d=4, t=10, seed=0)
    session = Session(cfg)
    session.fit(np.asarray(x, np.float32))
    session.score(np.asarray(x[:40], np.float32))
    return session.stats()


@pytest.mark.parametrize("kind", ["oneshot", "stream", "sharded"])
def test_session_stats_covers_every_topology(kind):
    with obs.using_registry(obs.MetricsRegistry()):
        snap = _session_snapshot(kind)
        h, c = snap["histograms"], snap["counters"]
        # serve latency histogram for this topology
        assert h[f"serve.latency{{topology={kind}}}"]["count"] == 40
        # refresh phase timings
        assert h[f"phase.refresh.fit{{topology={kind}}}"]["count"] >= 1
        # score phases
        assert h[f"phase.score.fused{{topology={kind}}}"]["count"] >= 1
        # kernel-backend dispatch counts
        assert any(k.startswith("kernels.dispatch{") for k in c)
        if kind == "oneshot":
            assert any(k.startswith("comm.records{") for k in c)
            assert any(k.startswith("comm.rows{") for k in c)
            assert any(k.startswith("phase.oneshot.site_summary{")
                       for k in h)
        if kind == "sharded":
            assert c["comm.rounds{topology=sharded}"] >= 1
            assert any(k.startswith("comm.bytes{") for k in c)
            assert any(k.startswith("comm.rows{") for k in c)


def test_session_stats_is_json_and_prom_renderable():
    with obs.using_registry(obs.MetricsRegistry()):
        snap = _session_snapshot("stream")
        json.dumps(snap)
        txt = obs.render_prometheus(snap)
        assert "serve_latency_bucket" in txt
