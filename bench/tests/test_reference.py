"""The reference comparison passes float32 distances computed at full
precision and fails those of one bfloat16 pass and of the control
(Precision.HIGH, three passes)."""
import numpy as np
import pytest

from bench import data, reference

LIMIT_ULPS = 16.0     # of the order of the configurations' dist_ulps


@pytest.fixture(scope="module")
def kdd_rows():
    x, _ = data.kdd_like(data.rng_for(3, 0), n=20_000, d=34, t_frac=0.0177)
    c = x[[0, 5000, 10000]].copy()
    return x[:8192], c


def served_from(dist, arg, thr):
    dist = np.asarray(dist, np.float64)
    return {"center": arg, "distance": dist, "score": dist / thr,
            "flag": dist / thr > 1.0}


def f32_expansion(x, c, cast=None):
    """x2 + c2 - 2 x.c in float32, the dot's inputs optionally rounded."""
    xd, cd = (x, c) if cast is None else (cast(x), cast(c))
    dot = xd.astype(np.float32) @ cd.astype(np.float32).T
    d = (np.square(x).sum(1)[:, None] + np.square(c).sum(1)[None, :]
         - np.float32(2) * dot).astype(np.float32)
    d = np.maximum(d, 0)
    return d.min(1), d.argmin(1)


def to_bf16(a):
    """Round float32 to bfloat16 (nearest even), kept as float32."""
    b = np.asarray(a, np.float32).view(np.uint32)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.view(np.float32)


def test_full_precision_passes(kdd_rows):
    x, c = kdd_rows
    thr = 40.0
    got = reference.score_numbers(x, served_from(*f32_expansion(x, c), thr),
                                  c, thr, LIMIT_ULPS)
    assert got["dist_ulps"] < LIMIT_ULPS / 4
    assert got["argmin_bad"] == got["flag_bad"] == got["missing"] == 0


def test_one_bf16_pass_fails(kdd_rows):
    x, c = kdd_rows
    thr = 40.0
    got = reference.score_numbers(
        x, served_from(*f32_expansion(x, c, to_bf16), thr), c, thr,
        LIMIT_ULPS)
    assert got["dist_ulps"] > 100 * LIMIT_ULPS


def test_control_fails(kdd_rows):
    x, c = kdd_rows
    thr = 40.0
    got = reference.score_numbers(x, reference.control_served(x, c, thr, emulate=True),
                                  c, thr, LIMIT_ULPS)
    assert got["dist_ulps"] > LIMIT_ULPS


def test_refresh_reference_finds_the_threshold(kdd_rows):
    x, c = kdd_rows
    w = np.ones(x.shape[0], np.float32)
    d, _, _ = reference.nearest(x, c)
    out = reference.mark_outliers(d, w, 80.0)
    assert out.sum() == 80
    model = {"centers": c, "threshold": float(np.float32(d[~out].max())),
             "trained_weight": float(x.shape[0])}
    got = reference.refresh_numbers(x, w, model, 80.0)
    assert got["thr_ulps"] < 1.0 and got["trained_gap"] == 0.0
    model["trained_weight"] -= 1
    assert reference.refresh_numbers(x, w, model, 80.0)["trained_gap"] == 1


def test_tree_numbers_catch_lost_mass_and_short_windows():
    ok = {"weights": np.full(10, 100.0), "spans": [(0, 600), (600, 1000)],
          "total": 1000, "window": 800}
    assert reference.tree_numbers([ok]) == {"mass_gap": 0.0,
                                            "window_short": 0.0}
    lost = dict(ok, weights=np.full(10, 90.0))
    assert reference.tree_numbers([lost])["mass_gap"] == 100.0
    short = dict(ok, spans=[(600, 1000)], weights=np.full(4, 100.0))
    assert reference.tree_numbers([short])["window_short"] == 400.0


def test_center_check_reads_a_fixed_point_low_and_a_moved_center_high(
        kdd_rows):
    x, c = kdd_rows
    w = np.ones(x.shape[0], np.float32)
    for _ in range(30):                  # float64 k-means-- to its fixed point
        c, _ = reference.lloyd_step(x, w, c, 80.0)
    fixed = c.astype(np.float32)
    assert reference.center_shift_ulps(x, w, fixed, 80.0) < 8.0
    moved = fixed.copy()
    moved[1] += 1e-3 * np.linalg.norm(moved[1])
    assert reference.center_shift_ulps(x, w, moved, 80.0) > 1e3
