"""Operations and bytes of one kernel call, from its shapes.

Copied from ``benchmarks/roofline.py`` (``_kernel_work``) so that the
yardstick lives with the benchmark: a later change to the program cannot
move it.  The peaks these are divided by sit in ``peaks.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def kernel_work(op: str, n: int, m: int, d: int) -> tuple[float, float]:
    """(flops, bytes) per call of one fused op at (n, m, d), f32.

    min_argmin: the l2 path is one (n,d)@(d,m) matmul plus the row
    reductions; lloyd_step adds the one-hot accumulate matmul (same FLOP
    count as the distance matmul); score is min_argmin plus the threshold
    divide (n more flops) and a third (n,)-shaped output.  Bytes model the
    streaming working set (read x and c, write the (n,)-shaped outputs),
    not the distance matrix, which the kernels never write to HBM.
    """
    dist_flops = 2.0 * n * m * d + 4.0 * n * m
    io_bytes = 4.0 * (n * d + m * d + 2 * n)
    if op == "lloyd_step":
        return dist_flops + 2.0 * n * m * d, io_bytes + 4.0 * (m * d + m)
    if op == "score":
        return dist_flops + float(n), io_bytes + 4.0 * n
    if op == "min_argmin":
        return dist_flops, io_bytes
    raise ValueError(f"no work function for kernel {op!r}")


def peaks(device_kind: str) -> dict:
    """The peaks of one chip by ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_seconds(op: str, n: int, m: int, d: int,
                     device_kind: str) -> tuple[float, str]:
    """Least time one call can take on the chip, and the bound that binds
    (``"compute"`` or ``"memory"``)."""
    flops, nbytes = kernel_work(op, n, m, d)
    pk = peaks(device_kind)
    t_c, t_m = flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
