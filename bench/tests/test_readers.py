"""The readers of the program's spans and counters, on a hand-made run:
what the window added, and nothing where the program has no such
series."""
from types import SimpleNamespace

import pytest

from bench import run as harness


def hist(count, total):
    return {"count": count, "sum": total}


def make_run(obs0, obs1):
    samples = {"t_open": 0.0, "t_close": 30.0, "refreshes": [],
               "spans": [], "obs0": obs0, "obs1": obs1}
    return harness.Run(SimpleNamespace(samples=samples, config={}), 1.0,
                       "TPU v5 lite", None)


def snap(counters=None, histograms=None):
    return {"counters": counters or {}, "histograms": histograms or {}}


def read(name, run):
    return harness.reader(name).read(run)


def test_refresh_upload_ms_is_the_window_mean():
    run = make_run(
        snap(histograms={"phase.refresh.upload{topology=stream}":
                         hist(2, 0.5)}),
        snap(histograms={"phase.refresh.upload{topology=stream}":
                         hist(6, 1.3)}))
    assert read("refresh_upload_ms", run) == pytest.approx(200.0)


def test_tick_ms_is_the_window_mean():
    run = make_run(snap(histograms={"phase.serve.tick": hist(10, 0.03)}),
                   snap(histograms={"phase.serve.tick": hist(1010, 4.03)}))
    assert read("tick_ms", run) == pytest.approx(4.0)


def test_gc_ms_adds_every_generation():
    run = make_run(
        snap(histograms={"phase.runtime.gc{gen=1}": hist(3, 0.003)}),
        snap(histograms={"phase.runtime.gc{gen=1}": hist(13, 0.013),
                         "phase.runtime.gc{gen=2}": hist(1, 0.25)}))
    assert read("gc_ms", run) == pytest.approx(260.0)
    # collections timed, none in the window: zero, not nothing
    quiet = snap(histograms={"phase.runtime.gc{gen=1}": hist(3, 0.003)})
    assert read("gc_ms", make_run(quiet, quiet)) == 0.0


def test_summary_h2d_mb_is_per_flush_or_merge():
    run = make_run(
        snap(counters={"summary.h2d_bytes": 1_000_000,
                       "tree.leaf_flushes{summarizer=auto}": 1}),
        snap(counters={"summary.h2d_bytes": 41_000_000,
                       "tree.leaf_flushes{summarizer=auto}": 4,
                       "tree.merges{summarizer=auto}": 2}))
    assert read("summary_h2d_mb", run) == pytest.approx(8.0)


@pytest.mark.parametrize("name", ["refresh_upload_ms", "tick_ms", "gc_ms",
                                  "summary_h2d_mb"])
def test_reader_finds_nothing_in_a_program_without_the_series(name):
    """A program without these spans and counters (the one before them)
    gives no value, and no error."""
    before = snap(counters={"tree.leaf_flushes{summarizer=auto}": 1},
                  histograms={"phase.refresh.fit{topology=stream}":
                              hist(1, 0.4)})
    after = snap(counters={"tree.leaf_flushes{summarizer=auto}": 9},
                 histograms={"phase.refresh.fit{topology=stream}":
                             hist(5, 2.0)})
    assert read(name, make_run(before, after)) is None
