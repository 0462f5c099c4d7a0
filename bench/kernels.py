"""Kernel device time from a traced run's profile."""
from __future__ import annotations

from typing import Optional

import trace_reduce


def per_call_seconds(run, needle: str) -> Optional[float]:
    """Mean device seconds of the profiled ops whose name or metadata holds
    ``needle`` (one op per call), or None where the profile has none."""
    p = run.profile
    if p is None:
        return None
    ops = trace_reduce.matching(p.ops, needle)
    if not ops:
        return None
    return sum(op.dur for op in ops) / len(ops) / 1e9
