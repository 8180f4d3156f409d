"""Busy union, idle share, kernel time and idle gaps on a hand-made trace
whose answers are known."""
import pytest

from bench import trace_reduce as tr

MS = 1_000_000  # ns


def hand_trace():
    # window [0, 100) ms; device 0 ops overlap: busy [10,30) u [50,60)
    dev0 = [(10 * MS, 20 * MS, "fusion.1"), (15 * MS, 30 * MS, "score_k"),
            (50 * MS, 60 * MS, "score_k"), (95 * MS, 120 * MS, "fusion.1")]
    dev1 = [(0, 50 * MS, "fusion.2")]
    host = [(0, 100 * MS, "bench.window"),
            (30 * MS, 50 * MS, "bench.refresh"),
            (35 * MS, 45 * MS, "PjitFunction(fit)"),
            (60 * MS, 95 * MS, "bench.ingest")]
    return tr.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host,
                    {})


def test_union_merges_and_clips():
    got = tr.union(hand_trace().devices["/device:TPU:0"], 0, 100 * MS)
    assert got == [(10 * MS, 30 * MS), (50 * MS, 60 * MS),
                   (95 * MS, 100 * MS)]


def test_busy_idle_and_kernels_one_device():
    r = tr.reduce(hand_trace(), devices=["/device:TPU:0"])
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["idle_share"] == pytest.approx(0.65)
    assert r["kernels"]["?:score_k"]["seconds"] == pytest.approx(0.025)
    assert r["kernels"]["?:score_k"]["calls"] == 2
    assert r["kernels"]["?:fusion.1"]["seconds"] == pytest.approx(0.015)
    assert r["device_ops"][0] == ["?:score_k", pytest.approx(0.025)]


def test_busy_is_averaged_over_devices():
    r = tr.reduce(hand_trace())
    assert r["busy_s"] == pytest.approx((0.035 + 0.05) / 2)


def test_idle_gaps_are_named_by_the_host():
    gaps = tr.reduce(hand_trace(), devices=["/device:TPU:0"])["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.035, 0.02, 0.01])
    assert gaps[0][0] == "bench.ingest"
    assert gaps[1][0] == "bench.refresh / PjitFunction(fit)"
    assert gaps[2][0] == "no bench annotation"


def test_window_comes_from_the_annotation():
    assert tr.window_of(hand_trace()) == (0, 100 * MS)
    with pytest.raises(ValueError):
        tr.window_of(tr.Trace({}, [], {}))


def test_operations_are_named_by_their_program():
    t = hand_trace()
    ops = [(10 * MS, 20 * MS, "%custom-call.3 = f32[256]{0} custom-call()"),
           (30 * MS, 32 * MS, "%fusion.7 = f32[256]{0} fusion()")]
    mods = [(9 * MS, 21 * MS, "jit__score_batch(77)"),
            (29 * MS, 33 * MS, "jit_fit(5)")]
    t = tr.Trace({"/device:TPU:0": ops}, t.host, {}, {},
                 {"/device:TPU:0": mods})
    k = tr.reduce(t)["kernels"]
    assert set(k) == {"jit__score_batch:custom-call.3", "jit_fit:fusion.7"}
