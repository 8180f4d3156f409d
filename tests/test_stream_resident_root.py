"""The refresh root assembled on the device from the tree's mirrors.

``StreamTree.device_root`` must equal ``packed_root()`` bit for bit under
every way the tree changes (leaf flushes, a partly filled leaf buffer,
eviction, merges of summaries of different sizes, spills and page-ins, a
checkpoint restore), upload each node at most once while it stays
resident, release a node's mirror when the node leaves the tree or the
host, and compile nothing once a root size has been met.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.store import StoreSpec
from repro.stream import ServiceConfig, StreamService, StreamTree, TreeConfig
from repro.stream.service import fit_model
from repro.stream.weighted import _bucket

DEV = jax.devices()[0]


def _points(n, d=3, seed=0):
    """Gaussian rows at three scales: summaries of several sizes."""
    rng = np.random.default_rng(seed)
    scale = rng.choice([0.1, 1.0, 10.0], size=(n, 1))
    return (rng.normal(size=(n, d)) * scale).astype(np.float32)


def _assert_bitwise(device_arrays, host_arrays):
    for a, b in zip(device_arrays, host_arrays, strict=True):
        a = np.asarray(a)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def _counter(name):
    return obs.snapshot()["counters"].get(f"{name}{{topology=stream}}", 0)


# ------------------------------------------------------------ bit identity
CASES = {
    # cadence a multiple of the leaf: the buffer is empty at each refresh
    "leaf_aligned": dict(tree=dict(leaf_size=512), batch=1024),
    # cadence off the leaf: the buffer holds rows at most refreshes
    "off_leaf": dict(tree=dict(leaf_size=512), batch=700),
    # a span cap of one leaf: no merges, old leaves evicted whole
    "window_eviction": dict(tree=dict(leaf_size=512, window=2048),
                            batch=1024),
    # binary-counter merges: summaries, and mirrors, of several sizes
    "merges_of_varied_size": dict(tree=dict(leaf_size=2048), batch=2048),
    # deep levels spilled, merges and refreshes page them back in
    "tiered_spill_page_in": dict(
        tree=dict(leaf_size=512, window=8192,
                  store=StoreSpec(hot_levels=0)), batch=1024),
    # halfway through, the tree is packed and restored with no mirrors
    "restored_checkpoint": dict(tree=dict(leaf_size=512, window=4096),
                                batch=1024, restore_at=8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_root_bit_identical_to_packed_root(case):
    spec = CASES[case]
    cfg = TreeConfig(dim=3, k=4, t=150, seed=3, **spec["tree"])
    x = _points(16_384)
    batch = spec["batch"]
    with obs.using_registry(obs.MetricsRegistry()):
        tree = StreamTree(cfg, device=DEV)
        spans, buffered = set(), 0
        for step, i in enumerate(range(0, len(x), batch)):
            if step == spec.get("restore_at"):
                tree = StreamTree.from_state(cfg, tree.pack_state(),
                                             device=DEV)
                assert all(nd.mirror is None for nd in tree.nodes)
                _, uploaded = tree.device_root()
                assert uploaded == tree.num_records
            tree.ingest(x[i:i + batch])
            root, _ = tree.device_root()
            _assert_bitwise(root, tree.packed_root())
            spans |= {_bucket(nd.n_records) for nd in tree.nodes}
            buffered += tree._buf_n > 0
        # each case reaches the path it names
        if case == "off_leaf":
            assert buffered >= 10
        if case == "window_eviction":
            assert tree.nodes[0].min_seq > 0
        if case == "merges_of_varied_size":
            assert len(spans) >= 2
        if case == "tiered_spill_page_in":
            st = tree.store.stats()
            assert st["spills"] >= 1 and st["page_ins"] >= 1
            assert any(nd.summary is None for nd in tree.nodes)


def test_service_model_and_scores_match_host_packed_root():
    """The model fit on the device-assembled root, and every score it
    gives, equal the host path's: ``fit_model`` on
    ``jnp.asarray(packed_root())`` with the same key."""
    cfg = ServiceConfig(dim=3, k=4, t=150, leaf_size=512, refresh_every=1024,
                        window=4096, seed=5)
    x = _points(8192, seed=1)
    svc = StreamService(cfg)
    svc.ingest(x[:7000])
    model = svc.refresh()
    version = int(model.version)
    pts, wts, valid = svc.tree.packed_root()
    host = fit_model(jnp.asarray(pts), jnp.asarray(wts), jnp.asarray(valid),
                     jax.random.fold_in(svc._model_key, version), version,
                     k=cfg.k, t=cfg.t, iters=cfg.second_iters,
                     metric=cfg.metric, policy=cfg.policy)
    _assert_bitwise(model, [np.asarray(a) for a in host])
    twin = StreamService(cfg)
    twin.model = host
    q = x[7000:]
    for a, b in zip(svc.score(q), twin.score(q), strict=True):
        assert (a.center, a.distance, a.outlier_score, a.is_outlier) == \
            (b.center, b.distance, b.outlier_score, b.is_outlier)


# ------------------------------------------------------------ engagement
def test_each_node_is_uploaded_once_while_resident():
    cfg = ServiceConfig(dim=3, k=4, t=150, leaf_size=512, refresh_every=1536,
                        window=6144, seed=2)
    x = _points(24_576, seed=2)
    with obs.using_registry(obs.MetricsRegistry()):
        svc = StreamService(cfg)
        seen: list = []   # every node ever mirrored, kept alive for id()
        before = (0, 0)
        for i in range(0, len(x), cfg.refresh_every):
            mirrored = {id(nd) for nd in seen}
            svc.ingest(x[i:i + cfg.refresh_every])   # one cadence refresh
            tree = svc.tree
            new = [nd for nd in tree.nodes if id(nd) not in mirrored]
            resident = _counter("refresh.root_rows_resident")
            uploaded = _counter("refresh.root_rows_uploaded")
            d_res, d_up = resident - before[0], uploaded - before[1]
            assert d_up == sum(nd.n_records for nd in new) + tree._buf_n
            assert d_res == tree.num_records - d_up
            assert all(nd.mirror is not None for nd in tree.nodes)
            seen.extend(new)
            before = (resident, uploaded)
        # the buffer holds rows at most refreshes, and leaves stay resident
        assert 0.4 < resident / (resident + uploaded) < 1.0


@pytest.mark.parametrize("way", ["evicted", "merged", "spilled"])
def test_mirrors_are_released_when_nodes_leave(way):
    tree_kw = {
        "evicted": dict(window=2048),             # span cap: no merges
        "merged": dict(),
        "spilled": dict(store=StoreSpec(hot_bytes=3 * 512 * 16)),
    }[way]
    cfg = TreeConfig(dim=3, k=4, t=150, leaf_size=512, seed=1, **tree_kw)
    x = _points(12_288, seed=3)
    with obs.using_registry(obs.MetricsRegistry()):
        tree = StreamTree(cfg, device=DEV)
        mirrored: list = []
        for i in range(0, len(x), 1024):
            tree.ingest(x[i:i + 1024])
            tree.device_root()
            mirrored += [nd for nd in tree.nodes if nd.mirror is not None
                         and all(nd is not m for m in mirrored)]
        live = {id(nd) for nd in tree.nodes}
        gone = [nd for nd in mirrored if id(nd) not in live]
        spilled = [nd for nd in mirrored
                   if id(nd) in live and nd.summary is None]
        for nd in gone + spilled:
            assert nd.mirror is None
        counts = obs.snapshot()["counters"]
        if way == "evicted":
            assert gone and counts["tree.evictions{summarizer=auto}"] >= 1
            assert "tree.merges{summarizer=auto}" not in counts
        if way == "merged":
            assert gone and counts["tree.merges{summarizer=auto}"] >= 1
        if way == "spilled":
            # a node mirrored while hot, then spilled, stays off the device
            # across refreshes that page it in
            assert spilled and tree.store.stats()["page_ins"] >= 1
        hot = [nd for nd in tree.nodes if nd.summary is not None]
        assert all(nd.mirror is not None for nd in hot)
        _assert_bitwise(tree.device_root()[0], tree.packed_root())


def test_async_refresh_inputs_survive_later_ingest():
    """The root handed to an async fit is its own array: ingest after
    ``_spawn_fit`` (leaf flushes, evictions, merges) changes neither the
    inputs nor the model."""
    cfg = ServiceConfig(dim=3, k=4, t=150, leaf_size=512, refresh_every=1 << 20,
                        window=3072, async_refresh=True, seed=4)
    x = _points(12_288, seed=4)
    svc = StreamService(cfg)
    svc.ingest(x[:4100])
    fits = []
    closure = svc._fit_closure
    svc._fit_closure = lambda v: fits.append(closure(v)) or fits[-1]
    svc.refresh(blocking=False)
    snapshot = svc.tree.packed_root()
    epoch = svc.tree.root_epoch
    svc.ingest(x[4100:])   # mirrors of the fit's nodes dropped meanwhile
    assert svc.tree.root_epoch > epoch
    svc.join_refresh()
    (fit,) = fits
    _assert_bitwise(fit.inputs, snapshot)
    version = int(svc.model.version)
    host = fit_model(*map(jnp.asarray, snapshot),
                     jax.random.fold_in(svc._model_key, version), version,
                     k=cfg.k, t=cfg.t, iters=cfg.second_iters,
                     metric=cfg.metric, policy=cfg.policy)
    _assert_bitwise(svc.model, [np.asarray(a) for a in host])


# ------------------------------------------------------------ compiles
def _uneven(n, sizes=(2048, 300, 500, 1200)):
    """Batch bounds of uneven sizes: the leaf buffer holds 300 to 2,000
    rows at a refresh, so its span changes under a root size met before."""
    i, k = 0, 0
    while i < n:
        yield i, min(n, i + sizes[k % len(sizes)])
        i, k = i + sizes[k % len(sizes)], k + 1


def test_no_compile_once_a_root_size_is_met():
    """The first assembly at a root size compiles the placements for every
    mirror span that size can hold; a later refresh at that size compiles
    nothing, even over a span it has not placed before."""
    compiled = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    # a width no other test of this file uses: none of its programs exist
    cfg = TreeConfig(dim=5, k=4, t=150, leaf_size=2048, seed=3)
    x = _points(16_384, d=5)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        tree = StreamTree(cfg, device=DEV)
        met, placed, fresh_spans = set(), set(), 0
        for i, j in _uneven(len(x)):
            tree.ingest(x[i:j])
            rows = _bucket(tree.num_records)
            spans = {_bucket(n) for n in
                     [nd.n_records for nd in tree.nodes] + [tree._buf_n] if n}
            compiled.clear()
            jax.block_until_ready(tree.device_root()[0])
            if rows in met:
                assert not compiled, (rows, spans)
                fresh_spans += bool(spans - placed)
            met.add(rows)
            placed |= spans
        assert fresh_spans >= 1
        # a second tree over the same stream compiles nothing at all
        again = StreamTree(cfg, device=DEV)
        compiled.clear()
        for i, j in _uneven(len(x)):
            again.ingest(x[i:j])
            jax.block_until_ready(again.device_root()[0])
        _assert_bitwise(again.device_root()[0], tree.packed_root())
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert not compiled, "the second pass compiled"
