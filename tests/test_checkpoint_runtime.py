"""Checkpoint manager, elastic runner, straggler monitor, robust
aggregation, data pipeline determinism, curation."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core.curation import CuratorConfig, DataCurator
from repro.data.tokens import PipelineConfig, TokenPipeline
from repro.runtime.straggler import StragglerMonitor


# ------------------------------------------------------------ checkpoint
def _tree(seed=0):
    k = jax.random.key(seed)
    return {"w": jax.random.normal(k, (8, 16)),
            "nested": {"b": jnp.arange(7, dtype=jnp.int32)},
            "step_scale": jnp.float32(3.5)}


def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(tmp_path)
    t = _tree(1)
    cm.save(5, t, blocking=True)
    like = jax.tree.map(jnp.zeros_like, t)
    restored, step = cm.restore(like)
    assert step == 5
    jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                         np.asarray(b)),
                 t, restored)


def test_checkpoint_async_latest_and_prune(tmp_path):
    cm = CheckpointManager(tmp_path, keep_last=2)
    for s in (1, 2, 3, 4):
        cm.save(s, _tree(s))
    cm.wait()
    assert cm.latest_step() == 4
    assert cm.all_steps() == [3, 4]


def test_checkpoint_crc_detects_corruption(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(1, _tree(2), blocking=True)
    d = cm.root / "step_000000001"
    f = sorted(d.glob("arr_*.npy"))[0]
    arr = np.load(f)
    arr = arr.reshape(-1)
    arr[0] += 1
    np.save(f, arr.reshape(np.load(f).shape))
    with pytest.raises(IOError):
        cm.restore(jax.tree.map(jnp.zeros_like, _tree(2)))


def test_checkpoint_interrupted_write_invisible(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(1, _tree(0), blocking=True)
    # simulate a crashed writer: stale tmp dir must be ignored
    (cm.root / "step_000000009.tmp").mkdir()
    assert cm.latest_step() == 1


def test_checkpoint_async_write_error_reraised(tmp_path, monkeypatch):
    """A failed async write must not die silently with the daemon thread:
    wait() re-raises it on the caller, and so does the next save() (which
    waits first), so dependent work cannot proceed past a lost step."""
    cm = CheckpointManager(tmp_path)
    orig = np.save

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(np, "save", boom)
    cm.save(1, _tree(0))             # async: enqueues, returns immediately
    with pytest.raises(OSError, match="disk full"):
        cm.wait()
    # the failed step was never published
    assert cm.all_steps() == []
    # error also surfaces on the next save(), not just an explicit wait():
    # save(3) joins the failed step-2 writer before touching disk itself
    cm.save(2, _tree(0))
    with pytest.raises(OSError, match="disk full"):
        cm.save(3, _tree(0), blocking=True)
    assert cm.all_steps() == []
    # ...and once drained the manager keeps working
    monkeypatch.setattr(np, "save", orig)
    cm.save(4, _tree(0), blocking=True)
    assert cm.latest_step() == 4


# ------------------------------------------------------------ data pipeline
def test_pipeline_deterministic_and_shard_disjoint():
    cfg = PipelineConfig(vocab=64, seq_len=32, global_batch=8, n_shards=4, seed=7)
    p = TokenPipeline(cfg)
    b1 = p.batch(10, 2)["tokens"]
    b2 = p.batch(10, 2)["tokens"]
    np.testing.assert_array_equal(b1, b2)           # resumable
    b3 = p.batch(10, 3)["tokens"]
    assert not np.array_equal(b1, b3)               # shards differ
    b4 = p.batch(11, 2)["tokens"]
    assert not np.array_equal(b1, b4)               # steps differ
    g = p.global_batch(10)["tokens"]
    assert g.shape == (8, 32)


# ------------------------------------------------------------ straggler
def test_straggler_monitor_flags_slow_site():
    mon = StragglerMonitor(n_sites=8, budget_frac=0.2)
    rng = np.random.default_rng(0)
    mask = None
    for _ in range(10):
        d = rng.normal(1.0, 0.02, size=8).astype(np.float32)
        d[3] = 4.0  # persistent straggler
        mask = mon.observe(d)
    assert mask[3]
    assert mask.sum() <= 2
    assert 3 in mon.policy(mask)


def test_straggler_monitor_quiet_when_healthy():
    mon = StragglerMonitor(n_sites=8)
    rng = np.random.default_rng(1)
    for _ in range(10):
        mask = mon.observe(rng.normal(1.0, 0.02, size=8).astype(np.float32))
    assert mask.sum() == 0


# ------------------------------------------------------------ curation
def test_curator_flags_planted_outlier_sequences():
    cur = DataCurator(n_sites=4, cfg=CuratorConfig(k=8, outlier_frac=0.02,
                                                   min_points=200))
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(8, 16)) * 3
    planted = []
    sid = 0
    for site in range(4):
        embs, ids = [], []
        for _ in range(400):
            c = rng.integers(0, 8)
            e = centers[c] + rng.normal(scale=0.05, size=16)
            if rng.random() < 0.02:
                e = e + rng.uniform(-30, 30, size=16)
                planted.append(sid)
            embs.append(e), ids.append(sid)
            sid += 1
        cur.observe(site, np.stack(embs), np.array(ids))
    flagged, comm = cur.detect()
    assert flagged is not None and comm > 0
    rec = len(set(flagged.tolist()) & set(planted)) / max(len(planted), 1)
    assert rec >= 0.7
    w = cur.sample_weights(np.array(planted), flagged)
    assert w.mean() <= 0.3


# ------------------------------------------------------------ elastic (subprocess)
_ELASTIC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.manager import CheckpointManager
    from repro.runtime.elastic import ElasticConfig, ElasticRunner

    D = 16

    def make_step(mesh):
        spec = NamedSharding(mesh, P())
        bspec = NamedSharding(mesh, P("data"))
        @jax.jit
        def step(state, batch):
            w, opt_step = state
            x, y = batch
            def loss_fn(w):
                pred = x @ w
                return jnp.mean((pred - y) ** 2)
            loss, g = jax.value_and_grad(loss_fn)(w)
            return (w - 0.1 * g, opt_step + 1), {"loss": loss}
        def run(state, batch):
            x = jax.device_put(batch["x"], bspec)
            y = jax.device_put(batch["y"], bspec)
            st = jax.device_put(state, (spec, spec))
            return step(st, (x, y))
        return run

    def init_state(mesh):
        return (jnp.zeros((D,)), jnp.int32(0))

    def shardings(mesh, state):
        s = NamedSharding(mesh, P())
        return (s, s)

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=D)
    def data_fn(step):
        r = np.random.default_rng(step)
        x = r.normal(size=(8, D)).astype(np.float32)
        return {"x": x, "y": (x @ w_true).astype(np.float32)}

    import tempfile
    ckpt = CheckpointManager(tempfile.mkdtemp())
    runner = ElasticRunner(make_step=make_step, init_state=init_state,
                           state_shardings=shardings, data_fn=data_fn,
                           ckpt=ckpt, cfg=ElasticConfig(ckpt_every=5))
    state, log = runner.run(60, fail_at={23: 4, 41: 2})
    print(json.dumps({
        "final_loss": log["losses"][-1],
        "remeshes": log["remesh_steps"],
        "devices_seen": sorted(set(log["device_counts"]), reverse=True),
    }))
""")


@pytest.mark.slow
def test_elastic_runner_survives_failures_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _ELASTIC], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res["remeshes"]) == 2          # two injected failures
    assert res["devices_seen"] == [8, 4, 2]   # elastic shrink path
    assert res["final_loss"] < 1e-2           # training still converges


# ------------------------------------------------------------ robust agg (subprocess)
_ROBUST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.runtime.robust_agg import robust_mean_grads

    mesh = jax.make_mesh((8,), ("data",))
    D = 32

    def per_replica(g):
        mean, (n_honest, flagged) = robust_mean_grads(
            {"w": g[0]}, "data", byzantine_budget=2)
        return mean["w"][None], jnp.stack([n_honest.astype(jnp.float32),
                                           flagged.astype(jnp.float32)])[None]

    fn = jax.shard_map(per_replica, mesh=mesh, in_specs=P("data"),
                       out_specs=(P("data"), P("data")))
    rng = np.random.default_rng(0)
    base = rng.normal(size=D).astype(np.float32)
    grads = np.stack([base + rng.normal(scale=0.01, size=D).astype(np.float32)
                      for _ in range(8)])
    grads[5] = 1000.0  # corrupted replica
    mean, info = fn(jnp.asarray(grads))
    mean = np.asarray(mean)[0]
    info = np.asarray(info)
    err_robust = float(np.abs(mean - base).max())
    err_naive = float(np.abs(grads.mean(0) - base).max())
    print(json.dumps({"robust": err_robust, "naive": err_naive,
                      "honest": float(info[0, 0]),
                      "flagged5": float(info[5, 1])}))
""")


@pytest.mark.slow
def test_robust_aggregation_masks_byzantine_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _ROBUST], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flagged5"] == 1.0             # the corrupted replica is caught
    # k-means-- flags at most byzantine_budget replicas; the significance
    # gate may keep or drop the second (borderline honest) one
    assert res["honest"] >= 6.0
    assert res["robust"] < 0.05               # paper primitive fixes the mean
    assert res["naive"] > 10.0                # naive averaging is destroyed
