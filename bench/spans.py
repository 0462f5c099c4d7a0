"""Reading the program's span trees (``Result.trace`` records, kept per
request by the harness) for the per-layer metrics."""
from __future__ import annotations

from typing import Callable, Dict, List, Optional


def quiet(run) -> list:
    """The window's outcomes that do not overlap the time the device
    profiler held the host (in a traced run it stalls the host for up to a
    second, which is the profiler's cost and not the program's)."""
    active = run.extra.get("profiled")
    outs = run.window.outcomes
    if not active:
        return list(outs)
    lo, hi = active
    return [o for o in outs
            if (o.done if o.ok else run.window.deadline) < lo or o.due > hi]


def served(run) -> List[dict]:
    """The summarized reports of the served requests outside the
    profiler's time."""
    return [o.report for o in quiet(run) if o.ok]


def traced(run) -> Optional[List[List[dict]]]:
    """Each served request's span records, or None when the run recorded
    no spans."""
    out = [r["spans"] for r in served(run)]
    if not out or any(s is None for s in out):
        return None
    return out


def self_seconds(spans: List[dict]) -> Dict[str, float]:
    """sid -> the span's seconds less what its children cover."""
    child = {}
    for s in spans:
        p = s.get("parent")
        if p is not None:
            child[p] = child.get(p, 0.0) + s["seconds"]
    return {s["sid"]: max(0.0, s["seconds"] - child.get(s["sid"], 0.0))
            for s in spans}


def mean_per_request(run, pick: Callable[[List[dict]], float]
                     ) -> Optional[float]:
    """The mean over served requests of ``pick(spans)`` (seconds), or None
    without spans."""
    reqs = traced(run)
    if not reqs:
        return None
    return sum(pick(s) for s in reqs) / len(reqs)


def named(spans: List[dict], *names: str) -> List[dict]:
    return [s for s in spans if s["name"] in names]
