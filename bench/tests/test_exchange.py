"""The readers of the four-site exchange, on a hand-made run and trace,
and the work function of an all-gather on the forms the compiler gives
it: one array, a tuple, and an asynchronous start/done pair."""
from types import SimpleNamespace

import pytest

from bench import run as harness
from bench import work_exchange

ICI = 1600e9 / 8     # bytes/s, peaks.json's TPU v5 lite

SYNC = ("%all-gather.16 = f32[4,131072,34]{1,2,0:T(8,128)S(1)} "
        "all-gather(%copy.37), channel_id=1, replica_groups={{0,1,2,3}}, "
        "dimensions={0}, use_global_device_ids=true")
TUPLE = ("%all-gather.3 = (f32[4,8,34]{2,1,0}, f32[4,8]{1,0}, "
         "pred[4,8]{1,0}) all-gather(f32[1,8,34]{2,1,0} %p, "
         "f32[1,8]{1,0} %w, pred[1,8]{1,0} %v), dimensions={0}")
START = ("%all-gather-start.1 = (f32[1,131072]{1,0:T(1,128)}, "
         "f32[4,131072]{1,0:T(1,128)}) all-gather-start(%bitcast.95), "
         "channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}")
DONE = ("%all-gather-done.1 = f32[4,131072]{1,0:T(1,128)} "
        "all-gather-done(%all-gather-start.1)")
OTHER = ("%fusion.79 = f32[524288]{0} fusion(%all-gather.16), "
         "kind=kLoop, calls=%fused_computation.79")


def test_op_kind_of_one_array_a_tuple_and_a_pair():
    assert work_exchange.op_kind(SYNC) == "all-gather"
    assert work_exchange.op_kind(TUPLE) == "all-gather"
    assert work_exchange.op_kind(START) == "all-gather-start"
    assert work_exchange.op_kind(DONE) == "all-gather-done"
    assert work_exchange.op_kind(OTHER) is None
    # the reduced trace keeps the first 200 characters of a text
    assert work_exchange.op_kind(DONE.split("]", 1)[0]) == "all-gather-done"


def test_received_bytes_and_least_time():
    # a chip holds its own quarter of the gathered payload already
    gathered = 4 * 131072 * (34 * 4 + 4 + 1)
    assert work_exchange.received_bytes(gathered, 4) == \
        3 * 131072 * (34 * 4 + 4 + 1)
    assert work_exchange.least_seconds(gathered, 4, "TPU v5 lite") == \
        pytest.approx(3 * 131072 * 141 / ICI)
    with pytest.raises(KeyError, match="no peaks"):
        work_exchange.least_seconds(gathered, 4, "TPU v99 imaginary")


def make_run(kernels=None, obs0=None, obs1=None, sites=4):
    samples = {"t_open": 0.0, "t_close": 30.0, "refreshes": [], "spans": [],
               "obs0": obs0 or {"counters": {}, "histograms": {}},
               "obs1": obs1 or {"counters": {}, "histograms": {}}}
    trace = None if kernels is None else {"kernels": kernels}
    cell = SimpleNamespace(samples=samples,
                           config={"pipeline": {"sites": sites}})
    return harness.Run(cell, 1.0, "TPU v5 lite", trace)


def read(name, run):
    return harness.reader(name).read(run)


def op(text, calls, seconds):
    return {"text": text, "calls": calls, "seconds": seconds}


def comm(rows_per_round, rounds, rec=None, sites=4):
    """The comm counters of ``rounds`` refreshes that each put
    ``rows_per_round`` padded rows (141 B each) per site on the exchange."""
    rows = rows_per_round * rounds
    c = {"comm.rounds{topology=sharded}": rounds}
    for i in range(sites):
        c[f"comm.records{{site={i},topology=sharded}}"] = (
            rows if rec is None else rec * rounds)
        c[f"comm.rows{{site={i},topology=sharded}}"] = rows
        c[f"comm.bytes{{site={i},topology=sharded}}"] = rows * 141
    return {"counters": c, "histograms": {}}


LEAST = 3 * 131072 * 141 / ICI      # one refresh of 131,072 rows a site


def test_exchange_roofline_of_synchronous_all_gathers(capsys):
    run = make_run({"jit_wrapped:all-gather.16": op(SYNC, 10, 30 * LEAST),
                    "jit_wrapped:all-gather.14": op(DONE, 10, 10 * LEAST),
                    "jit_wrapped:fusion.79": op(OTHER, 10, 1.0)},
                   obs0=comm(131072, 2), obs1=comm(131072, 12))
    assert read("exchange_roofline", run) == pytest.approx(25.0)
    err = capsys.readouterr().err
    assert "all-gather.16 10 calls" in err and "fusion" not in err


def test_exchange_roofline_of_a_start_done_pair(capsys):
    run = make_run({"m:all-gather-start.1": op(START, 5, 6 * LEAST),
                    "m:all-gather-done.1": op(DONE, 5, 4 * LEAST)},
                   obs1=comm(131072, 5))
    # the pair's time is both halves
    assert read("exchange_roofline", run) == pytest.approx(50.0)
    assert "start/done pair" in capsys.readouterr().err


def test_exchange_roofline_counts_the_bytes_of_every_refresh(capsys):
    """One op name stands for the program compiled at each root size, and
    the trace keeps one text per name: the bytes come from the program's
    count of each refresh, whatever size the text shows."""
    big = SYNC.replace("131072", "262144")
    run = make_run({"jit_wrapped:all-gather.16": op(big, 2, 6 * LEAST)},
                   obs1={"counters": {
                       k: comm(131072, 1)["counters"][k]
                       + comm(262144, 1)["counters"][k]
                       for k in comm(1, 1)["counters"]}, "histograms": {}})
    assert read("exchange_roofline", run) == pytest.approx(50.0)
    assert f"{3 * 393216 * 141 / 1e6:.3f} MB received" in \
        capsys.readouterr().err


def test_exchange_roofline_finds_nothing_without_bytes():
    run = make_run({"jit_wrapped:all-gather.16": op(SYNC, 10, 1.0)})
    assert read("exchange_roofline", run) is None


def test_exchange_roofline_finds_nothing_without_an_all_gather(capsys):
    run = make_run({"jit__kmeans_minus_minus:fusion.79": op(OTHER, 3, 1.0)})
    assert read("exchange_roofline", run) is None
    assert "no all-gather" in capsys.readouterr().err
    assert read("exchange_roofline", make_run(None)) is None


def test_gathered_valid_pct_is_records_over_rows_in_the_window():
    def counters(rec, rows, rounds):
        c = {"comm.rounds{topology=sharded}": rounds}
        for i in range(4):
            c[f"comm.records{{site={i},topology=sharded}}"] = rec
            c[f"comm.rows{{site={i},topology=sharded}}"] = rows
            c[f"comm.bytes{{site={i},topology=sharded}}"] = rows * 141
        return {"counters": c, "histograms": {}}
    run = make_run(obs0=counters(1000, 2048, 1),
                   obs1=counters(1000 + 3 * 750, 2048 + 3 * 1024, 4))
    assert read("gathered_valid_pct", run) == pytest.approx(
        100.0 * 2250 / 3072)
    # gathered_mb: the four sites' bytes of one round
    assert read("gathered_mb", run) == pytest.approx(4 * 1024 * 141 / 1e6)


def test_gathered_valid_pct_finds_nothing_without_the_rows_counter():
    """The program before ``comm.rows`` counts records and bytes only."""
    def counters(n):
        return {"counters": {"comm.records{site=0,topology=sharded}": n,
                             "comm.bytes{site=0,topology=sharded}": 9 * n,
                             "comm.rounds{topology=sharded}": n // 100},
                "histograms": {}}
    assert read("gathered_valid_pct", make_run(obs0=counters(100),
                                               obs1=counters(400))) is None
