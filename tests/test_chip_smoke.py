"""CPU rehearsal of ``chip_smoke.py`` and the bring-up repairs around it.

``chip_smoke.py`` has no CPU mode: on the chip it runs the served path at
the KDD Cup 1999 10% shape.  Here its phase functions run at a tiny size
on the CPU with every check except the TPU-only ones (Pallas served every
op, ``tpu_custom_call`` in the lowered programs, quality against the CPU
figure); the four-chip phases run on four virtual CPU devices.  Also: the
script refuses a CPU-only process, its host-reference check catches a
bf16-sized distance error, the precision probe runs, and the compile-cache
helper keeps the cache where it should.
"""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent

TINY = dict(n=6000, d=6, leaf_size=512, refresh_every=1536, ingest_batch=700,
            query_outliers=40, query_inliers=120, micro_batch=64)
# four sites: the KDD width, and enough points per attack cluster that the
# oneshot outlier-mass checks hold as they do at the KDD shape
FOUR = dict(TINY, n=60_000, d=34, leaf_size=4096, refresh_every=1 << 20,
            ingest_batch=8192)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod     # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


def test_served_path_rehearsal_on_cpu(smoke):
    rep = smoke.run_served_path(smoke.Size(**TINY))
    assert rep.ok, rep.checks
    assert set(rep.checks) == {
        "tree_summarizes", "score_stream_bit_identical", "argmin_vs_host",
        "distance_vs_host", "score_vs_host", "is_outlier_vs_host"}
    # off the chip auto selection serves every main-path op with "blocked"
    assert {op: set(b) for op, b in rep.backends.items()} == {
        "min_argmin": {"blocked"}, "lloyd_step": {"blocked"},
        "score": {"blocked"}}
    assert rep.quality["planted"] == TINY["query_outliers"]
    assert 0.0 <= rep.quality["recall"] <= 1.0


def test_host_reference_catches_bf16_sized_error(smoke):
    """The distance check must fail for a matmul at bf16 precision: an
    error of 2^-8 of |x||c| is far outside the f32 tolerance."""
    rng = np.random.default_rng(0)
    q = rng.normal(size=(64, 34)).astype(np.float32)
    c = rng.normal(size=(3, 34)).astype(np.float32)
    thr = np.float32(60.0)
    ref = smoke.host_reference(q, c, thr)

    class R:   # the QueryResult fields the check reads
        def __init__(self, i, dist):
            self.center, self.distance = int(ref["center"][i]), float(dist)
            self.outlier_score = float(np.float32(dist) / thr)
            self.is_outlier = self.outlier_score > 1.0

    exact = smoke.Report()
    smoke.check_reference(exact, [R(i, d) for i, d in enumerate(ref["dist"])],
                          ref)
    assert exact.ok, exact.checks
    scale = np.linalg.norm(q, axis=1) * np.linalg.norm(c[ref["center"]],
                                                       axis=1)
    bf16 = smoke.Report()
    smoke.check_reference(
        bf16, [R(i, d) for i, d in enumerate(ref["dist"] + scale / 256)],
        ref)
    assert not bf16.checks["distance_vs_host"]


def test_chip_smoke_refuses_a_cpu_only_process():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr and "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


_FOUR = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, ".")
    import chip_smoke as s
    rep = s.run_four_chips(s.Size(**{tiny!r}), n_sites=4)
    print("CHECKS " + json.dumps(rep.checks))
""")


@pytest.mark.slow
def test_four_chip_rehearsal_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _FOUR.format(tiny=FOUR)],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("CHECKS ")]
    assert json.loads(line[-1][len("CHECKS "):]) == {
        "sharded_shard_map_path": True, "sharded_model_bit_identical": True,
        "sharded_model_on_one_device": True,
        "sharded_scores_bit_identical": True,
        "oneshot_session_vs_distributed_cluster": True,
        "oneshot_planted_mass_in_summary": True,
        "oneshot_outlier_mass_recall": True}


def test_precision_probe_runs_off_the_chip(capsys):
    """``benchmarks/precision_probe.py`` at a small size: off a TPU every
    precision is plain f32, well inside the smoke's distance tolerance."""
    spec = importlib.util.spec_from_file_location(
        "precision_probe", ROOT / "benchmarks" / "precision_probe.py")
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    assert probe.main(["--n", "1024"]) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line[len("PRECISION_PROBE "):])
    for name in probe.PRECISIONS:
        assert out[f"xla_{name}"] < 64 and out[f"pallas_{name}"] < 64
    assert out["kernel_argmin_mismatch"] == 0


# ------------------------------------------------------------ compile cache
def test_compile_cache_honours_env_var(monkeypatch):
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    # JAX reads the variable itself; the helper names no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable() == path          # stable across calls
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
