"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle
share, kernel time by name and the longest idle gaps.

``load`` reads the file with JAX's own ``ProfileData`` into plain tuples;
``reduce`` works on those tuples only, so the arithmetic is checked on a
small hand-made trace in ``tests/test_trace_reduce.py``.

* busy: the union of the intervals in which an operation ran on a device,
  clipped to the traced window, averaged over the devices used;
* idle share: 1 - busy / window;
* kernel time: the summed device durations of the operations of one name,
  named ``<program>:<operation>`` (the ``XLA Modules`` event around it and
  the HLO instruction's name);
* idle gaps: the longest stretches with no operation on the first device,
  each named by the innermost ``bench.*`` host annotation and the
  innermost other host event that cover its middle.
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple

# the line of a device plane that holds one event per executed operation;
# "XLA Modules" (one event per program) is the fallback
OP_LINES = ("XLA Ops", "XLA Modules")
WINDOW_ANNOTATION = "bench.window"


class Trace(NamedTuple):
    devices: dict      # plane name -> [(start_ns, end_ns, op text)]
    host: list         # [(start_ns, end_ns, name)] from every host line
    op_line: dict      # plane name -> which line the ops came from
    lines: dict = {}   # device plane name -> the names of all its lines
    modules: dict = {}  # plane name -> [(start_ns, end_ns, program name)]


def op_name(text: str) -> str:
    """``%fusion.79 = f32[...] fusion(...)`` -> ``fusion.79``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_name(text: str) -> str:
    """``jit__score_batch(1234)`` -> ``jit__score_batch``."""
    return text.split("(", 1)[0].strip()


def _module_at(mods: list, t: float) -> str:
    """The program (``XLA Modules`` event) running at time t."""
    import bisect
    i = bisect.bisect_right([m[0] for m in mods], t) - 1
    if i >= 0 and mods[i][0] <= t < mods[i][1]:
        return module_name(mods[i][2])
    return "?"


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:")
            and not p.name.startswith("/device:CUSTOM")]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest under a profile dir)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    pd = ProfileData.from_file(path)
    devices, op_line, names, modules = {}, {}, {}, {}
    for plane in _device_planes(pd):
        lines = {ln.name: ln for ln in plane.lines}
        names[plane.name] = sorted(lines)
        want = next((w for w in OP_LINES if w in lines), None)
        if want is None:
            continue
        devices[plane.name] = [
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in lines[want].events]
        op_line[plane.name] = want
        if "XLA Modules" in lines and want != "XLA Modules":
            modules[plane.name] = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in lines["XLA Modules"].events)
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in ln.events if e.duration_ns > 0)
    return Trace(devices, host, op_line, names, modules)


def window_of(trace: Trace) -> tuple[float, float]:
    """[start, end) in ns of the benchmark's ``bench.window`` annotation."""
    spans = [(s, e) for s, e, n in trace.host if n == WINDOW_ANNOTATION]
    if not spans:
        raise ValueError(f"no {WINDOW_ANNOTATION!r} annotation in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals of ``intervals`` clipped to [lo, hi)."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(host, mid: float) -> str:
    """Innermost bench annotation and innermost other host event at mid."""
    cover = [(e - s, n) for s, e, n in host if s <= mid < e
             and n != WINDOW_ANNOTATION]
    bench = sorted(c for c in cover if c[1].startswith("bench."))
    other = sorted(c for c in cover if not c[1].startswith("bench."))
    parts = [bench[0][1] if bench else "no bench annotation"]
    if other:
        parts.append(other[0][1])
    return " / ".join(parts)


def reduce(trace: Trace, devices: list[str] | None = None,
           window: tuple[float, float] | None = None, top: int = 10) -> dict:
    """Busy, idle share, kernel times and idle gaps over the window.

    ``devices``: the planes of the chips the cell uses (default: every
    device plane that holds operations).  Times in the result are seconds.
    """
    lo, hi = window if window is not None else window_of(trace)
    names = devices if devices is not None else sorted(trace.devices)
    names = [n for n in names if n in trace.devices]
    if not names:
        raise ValueError("the trace holds no device operations")
    span = (hi - lo) * 1e-9
    busy, kernels, texts = [], {}, {}
    for name in names:
        evs = trace.devices[name]
        mods = trace.modules.get(name, [])
        busy.append(sum(e - s for s, e in union(evs, lo, hi)) * 1e-9)
        for s, e, text in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op = f"{_module_at(mods, s)}:{op_name(text)}"
                texts.setdefault(op, text[:200])
                kernels.setdefault(op, [0.0, 0])
                kernels[op][0] += d * 1e-9 / len(names)
                kernels[op][1] += 1.0 / len(names)
    busy_s = sum(busy) / len(names)
    merged = union(trace.devices[names[0]], lo, hi)
    gaps, cur = [], lo
    for s, e in merged:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": span,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / span if span > 0 else None,
        # per device: seconds and calls averaged over the devices used
        "kernels": {op: {"seconds": t, "calls": c, "text": texts[op]}
                    for op, (t, c) in kernels.items()},
        "device_ops": [[op, v[0]] for op, v in sorted(
            kernels.items(), key=lambda kv: -kv[1][0])[:top]],
        "idle_gaps": [[_label(trace.host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }
