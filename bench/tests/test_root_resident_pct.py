"""The ``root_resident_pct`` reader on a hand-made run: the window's
share of live root rows already on the device, and nothing where the
program does not count them."""
from types import SimpleNamespace

import pytest

from bench import run as harness


def make_run(obs0, obs1):
    samples = {"t_open": 0.0, "t_close": 30.0, "refreshes": [],
               "spans": [], "obs0": obs0, "obs1": obs1}
    return harness.Run(SimpleNamespace(samples=samples, config={}), 1.0,
                       "TPU v5 lite", None)


def snap(counters=None):
    return {"counters": counters or {}, "histograms": {}}


def read(run):
    return harness.reader("root_resident_pct").read(run)


def test_root_resident_pct_is_the_windows_resident_share():
    run = make_run(
        snap({"refresh.root_rows_resident{topology=stream}": 100,
              "refresh.root_rows_uploaded{topology=stream}": 900}),
        snap({"refresh.root_rows_resident{topology=stream}": 700,
              "refresh.root_rows_uploaded{topology=stream}": 1100}))
    assert read(run) == pytest.approx(75.0)


def test_root_resident_pct_finds_nothing_without_the_counters():
    """A program without the counters (the one before them) gives no
    value, and no error."""
    before = snap({"tree.leaf_flushes{summarizer=auto}": 1})
    after = snap({"tree.leaf_flushes{summarizer=auto}": 9})
    assert read(make_run(before, after)) is None
