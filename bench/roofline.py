"""Peaks of the chip and the least time a kernel's work could take on it.

The peaks live in ``peaks.json``, keyed by JAX's ``device_kind``; a device
that is not in the table is an error, never a default.  The work is what
the algorithm needs, computed from its shapes by the functions below, so a
change to how a kernel does its work cannot move the yardstick.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> Dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{', '.join(sorted(table))}")
    return table[device_kind]


def least_seconds(ops: float, nbytes: float, device_kind: str,
                  precision: str = "bf16") -> Tuple[float, str]:
    """The larger of ops over peak operations per second and bytes over
    peak bandwidth, and which of the two bounds it."""
    pk = peaks(device_kind)
    t_ops = ops / pk["flops_per_s"][precision]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def share_percent(ops: float, nbytes: float, seconds: float,
                  device_kind: str, precision: str = "bf16") -> float:
    """The least time as a percentage of the measured time."""
    if seconds <= 0:
        raise ValueError(f"measured time must be positive, got {seconds}")
    return 100.0 * least_seconds(ops, nbytes, device_kind,
                                 precision)[0] / seconds


def haar_work(rows: int, samples: int) -> Tuple[float, float]:
    """(operations, bytes) of a multi-level Haar transform of a (rows,
    samples) float32 array: every sample read once and every coefficient
    written once, and an add and a multiply for each output of each of the
    levels (2 N T over the first level, halving with each level after it,
    4 N T in all)."""
    return 4.0 * rows * samples, 2.0 * rows * samples * 4
