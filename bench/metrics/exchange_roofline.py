"""exchange_roofline: the refresh's all-gathers' share of their
interconnect roofline: the least time a chip could take to receive its
share of the window's exchange at its ICI peak (``work_exchange.py`` on
the program's ``comm.bytes``, ``peaks.json``), over the operations'
device time per chip from the trace, in percent.

The time is the synchronous ``all-gather``'s, or the
``all-gather-start``/``-done`` pair's.  Nothing where the trace holds no
all-gather or the program counted no bytes."""
import sys

from bench import work_exchange


def read(run):
    if run.trace is None:
        return None
    found = {op: v for op, v in run.trace["kernels"].items()
             if work_exchange.op_kind(v["text"]) is not None}
    if not found:
        print("exchange_roofline: no all-gather in the trace",
              file=sys.stderr)
        return None
    sites = int(run.config["pipeline"]["sites"])
    gathered = run.counter("comm.bytes")
    seconds = sum(v["seconds"] for v in found.values())
    kinds = {work_exchange.op_kind(v["text"]) for v in found.values()}
    form = ("start/done pair" if "all-gather-done" in kinds
            else "synchronous all-gather")
    print(f"exchange_roofline: {form}; " + ", ".join(
        f"{op} {v['calls']:.0f} calls {v['seconds']:.6f} s"
        for op, v in sorted(found.items()))
        + f"; {work_exchange.received_bytes(gathered, sites) / 1e6:.3f} MB "
        f"received per chip", file=sys.stderr)
    if seconds <= 0 or gathered <= 0:
        return None
    least = work_exchange.least_seconds(gathered, sites, run.device_kind)
    return 100.0 * least / seconds
