"""Bytes and least time of the refresh's exchange.

An all-gather over ``s`` sites hands every chip the whole gathered
payload, of which the chip already holds its own ``1/s``: it receives
``(s - 1) / s`` of the payload's bytes.  The program counts that payload
exactly (``comm.bytes``: every site's padded root, which is what the
all-gathers' results hold).  The least time divides the received bytes by
the chip's whole interconnect figure (``ici_bits_per_s`` of
``peaks.json``), which no wiring of the chips can beat.
"""
from __future__ import annotations

from bench.work import peaks

KINDS = ("all-gather-start", "all-gather-done", "all-gather")


def op_kind(text: str) -> str | None:
    """``all-gather``, ``all-gather-start`` or ``all-gather-done`` for an
    all-gather operation (by its instruction name), else None."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    base = name.split(".", 1)[0]
    return base if base in KINDS else None


def received_bytes(gathered: float, sites: int) -> float:
    """Bytes one chip receives of ``gathered`` bytes all-gathered over
    ``sites``."""
    return gathered * (sites - 1) / sites


def ici_bytes_per_s(device_kind: str) -> float:
    """The chip's whole interconnect figure, in bytes per second."""
    return peaks(device_kind)["ici_bits_per_s"] / 8.0


def least_seconds(gathered: float, sites: int, device_kind: str) -> float:
    """Least time one chip takes to receive its share of ``gathered``
    bytes at its interconnect peak."""
    return received_bytes(gathered, sites) / ici_bytes_per_s(device_kind)
