"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, kernel
device time by name, and the longest idle gaps labelled by what the host was
doing.

Device planes are those whose name starts with ``/device:TPU:``; their op
events come from the line named ``XLA Ops`` (all lines where a plane has
none).  A rehearsal on the CPU backend reads instead the host events that
carry an ``hlo_op`` stat, XLA's CPU ops, as the one "device".  Busy time is the union of the op intervals, so overlapping ops count
once.  Times are in nanoseconds from the start of the profile.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
# host functions that only say a thread was waiting or idle: never a gap's
# label (a python frame event is named "$<file>:<line> <function>")
_WAITING = frozenset(("wait", "acquire", "sleep", "join", "select", "recv",
                      "_recv", "_recv_bytes", "poll", "result", "get",
                      "_bootstrap", "_bootstrap_inner", "run", "_loop",
                      "client", "_wait_for_tstate_lock", "_worker",
                      "__enter__", "__exit__", "<module>", "main"))


@dataclass
class Op:
    name: str
    start: float       # ns
    dur: float         # ns
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur

    def text(self) -> str:
        """The name and every string stat, for matching a kernel by name."""
        return " ".join([self.name] + [str(v) for v in self.stats.values()
                                       if isinstance(v, str)])


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def _events(line, with_stats: bool = True) -> List[Op]:
    out = []
    for e in line.events:
        stats = {}
        if with_stats:
            try:
                stats = dict(e.stats)
            except Exception:         # noqa: BLE001 — stats are optional
                pass
        out.append(Op(e.name, float(e.start_ns), float(e.duration_ns), stats))
    return out


def load(path: str, cpu_ops: bool = False
         ) -> Tuple[Dict[str, List[Op]], List[Op]]:
    """(device plane name -> op events, host events of every thread).
    ``cpu_ops``: the XLA CPU ops of the host plane stand in for a device."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith(DEVICE_PREFIX):
            ops_lines = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices[plane.name] = [op for ln in ops_lines
                                   for op in _events(ln)]
        elif plane.name == HOST_PLANE:
            host = [op for ln in lines for op in _events(ln, False)]
            if cpu_ops:
                devices[HOST_PLANE] = [
                    op for ln in lines for op in _events(ln)
                    if "hlo_op" in op.stats]
    return devices, host


def clip(ops: Iterable[Op], lo: float, hi: float) -> List[Op]:
    """The ops' parts that lie in [lo, hi)."""
    out = []
    for op in ops:
        s, e = max(op.start, lo), min(op.end, hi)
        if e > s:
            out.append(Op(op.name, s, e - s, op.stats))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(ops: Iterable[Op]) -> float:
    """Time in which at least one op ran."""
    return sum(e - s for s, e in union((op.start, op.end) for op in ops))


def time_by_name(ops: Iterable[Op]) -> Dict[str, Tuple[int, float]]:
    """name -> (events, total ns)."""
    out: Dict[str, Tuple[int, float]] = {}
    for op in ops:
        n, t = out.get(op.name, (0, 0.0))
        out[op.name] = (n + 1, t + op.dur)
    return out


def matching(ops: Iterable[Op], needle: str) -> List[Op]:
    """Ops whose name or a string stat contains ``needle``."""
    return [op for op in ops if needle in op.text()]


def gaps(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle (start, end) intervals of [lo, hi) in which no op ran."""
    out, cur = [], lo
    for s, e in union((op.start, op.end) for op in ops):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def gap_label(gap: Tuple[float, float], host: Sequence[Op]) -> str:
    """The shortest host event that covers the whole gap and is not a
    thread waiting: what the host was doing while the device idled."""
    s, e = gap
    best: Optional[Op] = None
    for op in host:
        if op.start <= s and op.end >= e and op.dur > 0 \
                and op.name.rsplit(" ", 1)[-1] not in _WAITING:
            if best is None or op.dur < best.dur:
                best = op
    return best.name if best is not None else "(host waiting)"


@dataclass
class Reduced:
    """What the benchmark reads from one profile."""
    window_ns: float
    busy_ns: float                     # mean over the device planes
    devices: int
    ops: List[Op]                      # device ops wholly in the window
    top_ops: List[Tuple[str, float]]   # (name, seconds), largest first
    idle_gaps: List[Tuple[str, float]]


def reduce(path: str, window_ns: float, top: int = 10,
           cpu_ops: bool = False) -> Reduced:
    devices, host = load(path, cpu_ops)
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane in {path}")
    per_plane = {k: clip(v, 0.0, window_ns) for k, v in devices.items()}
    ops = [op for v in devices.values() for op in v
           if op.start >= 0.0 and op.end <= window_ns]
    busy = sum(busy_ns(v) for v in per_plane.values()) / len(per_plane)
    by = time_by_name(op for v in per_plane.values() for op in v)
    top_ops = sorted(((n, t / 1e9) for n, (_, t) in by.items()),
                     key=lambda kv: -kv[1])[:top]
    first = next(iter(sorted(per_plane)))
    idle = sorted(gaps(per_plane[first], 0.0, window_ns),
                  key=lambda g: g[0] - g[1])[:top]
    idle_gaps = [(gap_label(g, host), (g[1] - g[0]) / 1e9) for g in idle]
    return Reduced(window_ns, busy, len(per_plane), ops, top_ops, idle_gaps)
