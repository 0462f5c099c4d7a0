"""The program's spans on the clock of the device profile, for the readers of
idle time.

While a profiler runs, each traced request writes one ``bigdawg.clock_anchor``
annotation into the profile (profile nanoseconds; its ``request`` stat is
``r`` and the request's span id) and records a ``clock_anchor`` span around it (host
``perf_counter`` seconds, the clock of every span).  The two clocks differ by
one offset, the median over these pairs, which maps every span of every
request onto the profile.  A program whose spans carry no anchor gives no
timeline, and the readers then find nothing to read.

The device's idle intervals are those of ``trace_reduce``: the gaps between
the ops of each device plane inside ``[0, window_ns)``.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

ANCHOR = "bigdawg.clock_anchor"
TOP_GAPS = 10
# spans in which the host works on an engine op: dispatching it, or
# computing it on the host (a fused segment is one such op)
DISPATCH = ("engine_op", "fused_segment")


@dataclass
class Span:
    name: str
    label: str         # the name, with the op for an engine_op
    lo: float          # profile ns
    hi: float


@dataclass
class Timeline:
    window_ns: float
    idle_pct: float                        # idle_share, as its reader has it
    planes: List[List[trace_reduce.Op]]    # each device plane's ops
    spans: List[Span]
    host: List[trace_reduce.Op]            # host events, for unlabelled gaps


def _label(rec: dict) -> str:
    op = rec.get("attrs", {}).get("op")
    return f"{rec['name']}:{op}" if rec["name"] == "engine_op" and op \
        else rec["name"]


def anchor_events(path: str) -> Dict[str, Tuple[float, float]]:
    """request span id -> (start, end) ns of its anchor annotation."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    rid = dict(e.stats).get("request")
                    if isinstance(rid, str) and rid.startswith("r"):
                        out[rid[1:]] = (float(e.start_ns),
                                        float(e.start_ns + e.duration_ns))
    return out


def offset_ns(records: Sequence[dict],
              events: Dict[str, Tuple[float, float]]) -> Optional[float]:
    """Profile ns minus perf_counter ns, the median over the anchors found
    on both sides; None where none pairs."""
    diffs = []
    for r in records:
        ev = events.get(r["parent"])
        if ev is not None:
            diffs.append((ev[0] + ev[1]) / 2
                         - 1e9 * (r["start"] + r["seconds"] / 2))
    return statistics.median(diffs) if diffs else None


def load(run, trace_dir: Optional[str] = None) -> Optional[Timeline]:
    """The timeline of a traced run: its profile (read again from the
    directory the harness wrote it to) and the spans of every request that
    came back, mapped by the anchors.  None without a profile, spans or
    anchors.  Kept on ``run.extra`` for the next reader."""
    if "timeline" in run.extra:
        return run.extra["timeline"]
    tl = None
    recs = [s for o in run.window.outcomes if o.ok and o.report.get("spans")
            for s in o.report["spans"]]
    anchors = [s for s in recs if s["name"] == "clock_anchor"]
    if run.profile is not None and anchors:
        if trace_dir is None:
            import harness
            trace_dir = harness.TRACE_DIR
        path = trace_reduce.find_xplane(trace_dir)
        off = offset_ns(anchors, anchor_events(path))
        if off is not None:
            devices, host = trace_reduce.load(path)
            if not devices:        # the CPU backend: its XLA ops stand in
                devices, host = trace_reduce.load(path, cpu_ops=True)
            w = run.profile.window_ns
            tl = Timeline(
                w, 100.0 * (1.0 - run.profile.busy_ns / w),
                [trace_reduce.clip(v, 0.0, w)
                    for _, v in sorted(devices.items())],
                [Span(s["name"], _label(s), 1e9 * s["start"] + off,
                      1e9 * (s["start"] + s["seconds"]) + off)
                 for s in recs if s["name"] != "clock_anchor"], host)
    run.extra["timeline"] = tl
    return tl


def _overlap(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share_with(tl: Timeline, names: Sequence[str]) -> float:
    """Percent of the window in which the device was idle while at least
    one span of ``names`` was open: ``idle_share`` less the idle time with
    none open, averaged over the device planes as ``idle_share`` is (so it
    never reads above ``idle_share``)."""
    open_ = trace_reduce.union((s.lo, s.hi) for s in tl.spans
                               if s.name in names)
    bare = []
    for ops in tl.planes:
        gaps = trace_reduce.gaps(ops, 0.0, tl.window_ns)
        bare.append(max(0.0, sum(hi - lo for lo, hi in gaps)
                        - _overlap(gaps, open_)))
    return tl.idle_pct - 100.0 * sum(bare) / len(bare) / tl.window_ns


def gap_labels(tl: Timeline, top: int = TOP_GAPS
               ) -> List[Tuple[str, float]]:
    """The longest idle gaps of the first device plane, each labelled with
    the innermost span open over the whole gap on any thread, else with the
    host frame ``trace_reduce`` would give it: (label, seconds)."""
    gaps = sorted(trace_reduce.gaps(tl.planes[0], 0.0, tl.window_ns),
                  key=lambda g: g[0] - g[1])[:top]
    out = []
    for lo, hi in gaps:
        over = [s for s in tl.spans if s.lo <= lo and s.hi >= hi]
        label = min(over, key=lambda s: s.hi - s.lo).label if over \
            else trace_reduce.gap_label((lo, hi), tl.host)
        out.append((label, (hi - lo) / 1e9))
    return out
