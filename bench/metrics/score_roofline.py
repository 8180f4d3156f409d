"""score_roofline: the score kernel's share of its roofline: the least
time its calls in the window could take at the chip's peaks (``work.py``
at micro_batch x k x d, ``peaks.json``), over their device time from the
trace, in percent."""
import sys

from bench import work


def is_score_kernel(op: str, text: str) -> bool:
    """The Pallas call (a custom call) inside the served score program."""
    return "score" in op.partition(":")[0] and " custom-call(" in text


def read(run):
    if run.trace is None:
        return None
    found = {op: v for op, v in run.trace["kernels"].items()
             if is_score_kernel(op, v["text"])}
    seconds = sum(v["seconds"] for v in found.values())
    calls = sum(v["calls"] for v in found.values())
    if seconds <= 0 or calls <= 0:
        return None
    p = run.config["pipeline"]
    least, bound = work.roofline_seconds(
        "score", int(p["micro_batch"]), int(p["k"]), int(p["dim"]),
        run.device_kind)
    print(f"score_roofline: {sorted(found)} {calls:.0f} calls, "
          f"{seconds:.6f} s on the device; bound by {bound}",
          file=sys.stderr)
    return 100.0 * least * calls / seconds
