"""Plan-level kernel fusion: compile a cached plan's intra-engine chains
into single jitted callables (ROADMAP item 3; the runtime analogue of
gnitz's JIT-specialized kernels).

The executor dispatches cached plans node-by-node: every op pays a host
round trip (argument gather, engine shim call, async-dispatch bookkeeping)
even when the whole chain is pure device math.  The learned
``dispatch_overhead`` calibration from PR 4 says exactly how much time that
leaves on the table.  This module closes it for the dense/array family:

  segmentation  ``fuse_plan(query, plan)`` walks the post-order under the
      plan's engine assignment and groups maximal same-engine chains of
      *fusable* ops — ``matmul``, ``add``, ``scale``, ``transpose``,
      ``select``, ``haar``, ``tfidf``, ``knn``, ``count`` on the
      ``dense_array`` engine, whose implementations are pure jnp traces
      over ``DenseTensor.data`` (``count`` over its threaded valid-count —
      see below) — into ``FusedSegment``s.  A segment never
      crosses an engine boundary (members share one assignment) and never
      absorbs an island-boundary (``scope``) node: scope is not fusable, so
      every island seam breaks the chain and its cast stays an explicit,
      byte-accounted migrator edge.  Cast-in edges at a segment boundary
      (an external input homed on another data model) happen as part of the
      segment's single host task — the migrator casts them onto the engine
      before the compiled call, and everything between stays on device
      end-to-end.

  compilation  each segment lowers to one python function over raw jnp
      arrays — routing ``haar``/``knn`` through ``kernels.ops`` (so the
      Pallas kernels serve them on TPU, the jnp references elsewhere) and
      composing the rest as jnp — wrapped in a single ``jax.jit``.  The
      wrapped callable is cached process-wide under the segment's
      *structural key* (engine + per-member op/attrs + wiring); ``jax.jit``
      itself specializes per input (shapes, dtypes), so the full compile
      cache key is (segment signature, shapes, dtypes) and a warm serve of
      a previously-seen segment shape skips tracing entirely.  The
      middleware stores the ``FusedPlan`` on its ``CachedPlan`` entry
      (runtime-only — never persisted, like the alternate-rotation cursor).

  fallback  fusion must never change results.  A segment that cannot be
      traced as one program (``FUSION_REFUSALS``: a member needs a concrete
      value at trace time) marks the segment key *broken* in a process-wide
      registry (``mark_broken``) and the executor re-runs the members
      node-by-node in the same host task; later serves see the sticky mark
      and skip the fused attempt for that signature.
      ``ExecutionResult.fusion_fallbacks`` counts transitions, and the
      middleware rolls them up into ``stats["fusion_fallbacks"]``.  Any
      other failure — a compile error, a refused kernel, a device fault —
      propagates like the same failure of an unfused op: re-running it
      node by node would only hide it.

Equivalence notes (what the ``tests/test_fusion.py`` property battery
pins): member semantics mirror ``engines._da_*`` exactly — intermediates
flow ``.data`` plus a threaded valid-count value (``count`` is the one
dense op that consumes metadata instead of data: external counts enter
the trace as scalars, a ``select`` narrows the threaded count with its
mask sum, ``count`` reads it), so composing data-level functions is
identical to chaining containers; a ``select`` at the segment root
additionally returns its mask sum so the output's ``valid_count`` matches
the eager engine's (engine-produced tensors carry the default fill, which
the lowering also uses for member-to-member edges).  Queries with
shared subtrees (one uid at several post-order positions) are not fused —
segmentation is position-keyed so a ``FusedPlan`` survives query rebuilds,
and sharing would break the one-position-per-uid mapping.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import CostModel
from repro.core.ops import SCOPE_OP, PolyOp, Ref
from repro.core.planner import Plan, _work_elems, estimate_sizes_shapes
from repro.core.tables import DenseTensor

# the dense/array fusable family: every op here is a pure jnp trace over
# DenseTensor.data in engines.py.  ``count`` consumes valid_count METADATA
# rather than data, so the lowering threads a per-member valid-count value
# through the trace (external counts enter as traced scalars, a select's
# mask sum updates it, count reads it) — see ``_build_callable``.
# (distinct/bin_hist are still excluded; bin_hist is fusable in principle
# and a natural follow-on)
FUSABLE_OPS = frozenset({"matmul", "add", "scale", "transpose", "select",
                         "haar", "tfidf", "knn", "count"})

# engines whose fusable ops trace (dense/array family first — triple-format
# engines are numpy-eager in places and not jit-safe)
FUSABLE_ENGINES = frozenset({"dense_array"})

# a single-node "chain" gains nothing from fusion (one dispatch either way)
# and would pay a compile per attrs variant — segments need >= 2 members
MIN_SEGMENT_NODES = 2

# -- process-wide compiled-callable cache + sticky fallback registry --------
class FusionRefused(Exception):
    """A segment cannot be traced as one program.  Raised by fault
    injection (``runtime.fault.FusionFaultInjector``); JAX's own tracer
    errors below mean the same."""


# the only failures the executor answers by running a segment node by node
FUSION_REFUSALS = (FusionRefused, jax.errors.ConcretizationTypeError,
                   jax.errors.TracerBoolConversionError,
                   jax.errors.TracerIntegerConversionError,
                   jax.errors.TracerArrayConversionError)

_COMPILED: Dict[str, Callable] = {}
_BROKEN: Dict[str, str] = {}        # segment key -> failure description
_WARM: set = set()                  # (key, ext shapes/dtypes) runs completed
_REGISTRY_LOCK = threading.Lock()


def reset_cache() -> None:
    """Drop all compiled segment callables AND sticky fallback marks
    (tests; a long-lived process never needs this — jit caches are the
    point)."""
    with _REGISTRY_LOCK:
        _COMPILED.clear()
        _BROKEN.clear()
        _WARM.clear()


def is_broken(key: str) -> bool:
    with _REGISTRY_LOCK:
        return key in _BROKEN


def mark_broken(key: str, reason: str) -> None:
    """Sticky per-signature fallback: once a segment key failed to
    trace/compile/run fused, no later serve retries it."""
    with _REGISTRY_LOCK:
        _BROKEN.setdefault(key, reason)
        _COMPILED.pop(key, None)


def broken_keys() -> Dict[str, str]:
    with _REGISTRY_LOCK:
        return dict(_BROKEN)


@dataclass(frozen=True)
class FusedSegment:
    """One maximal fusable chain of a plan, keyed by post-order position so
    it survives query rebuilds (uids do not).

    ``input_specs[j]`` describes member j's arguments: ``("mem", i)`` is the
    i-th member's output (stays on device inside the trace); ``("ext", s)``
    is the s-th external input.  ``ext_sources[s]`` locates it at execute
    time: ``("ref", name)`` from the catalog, ``("pos", p)`` from the value
    another unit produced at post-order position p."""
    engine: str
    positions: Tuple[int, ...]           # members, dependency (post) order
    ops: Tuple[str, ...]
    attrs_list: Tuple[Tuple[Tuple[str, Any], ...], ...]   # sorted attr items
    input_specs: Tuple[Tuple[Tuple[str, int], ...], ...]
    ext_sources: Tuple[Tuple[str, Any], ...]
    weights: Tuple[float, ...]           # pro-rata time attribution, sums to 1
    key: str                             # structural signature (cache key)

    @property
    def root_pos(self) -> int:
        return self.positions[-1]


@dataclass
class FusedPlan:
    """The fusion pass's output for one (query shape, plan): the segments
    plus the exact structural fingerprint of the query it was built from.
    Signatures bin constant attrs, so two queries can share a signature yet
    differ in exact attr values — the compiled callables close over the
    build query's attrs, and the middleware compares ``fingerprint`` before
    reusing a cached FusedPlan (mismatch -> rebuild, not wrong answers)."""
    plan_key: str
    fingerprint: str
    segments: Tuple[FusedSegment, ...] = ()
    # optional runtime.fault.FusionFaultInjector: its on_fuse(key) hook
    # fires just before every fused invocation (the trace-refusal seam)
    injector: Any = None

    @property
    def n_fused_nodes(self) -> int:
        return sum(len(s.positions) for s in self.segments)


def query_fingerprint(query: PolyOp) -> str:
    """Exact structural identity of a query instance: islands, ops, EXACT
    attr values, and input wiring — everything a compiled segment closes
    over.  Cheaper than ``signature()`` (no hashing, no catalog) and
    stricter (signatures bin constants; this must not)."""
    parts: List[str] = []
    pos_of: Dict[int, int] = {}
    for pos, node in enumerate(query.nodes()):
        pos_of[node.uid] = pos
        ins = ",".join(f"r:{i.name}" if isinstance(i, Ref)
                       else f"n:{pos_of[i.uid]}" for i in node.inputs)
        attrs = ",".join(f"{k}={node.attrs[k]!r}"
                         for k in sorted(node.attrs))
        parts.append(f"{node.island}.{node.op}[{attrs}]({ins})")
    return ";".join(parts)


# ---------------------------------------------------------------------------
# segmentation
# ---------------------------------------------------------------------------

def fuse_plan(query: PolyOp, plan: Plan, catalog=None,
              cost_model: Optional[CostModel] = None,
              injector: Any = None,
              min_nodes: int = MIN_SEGMENT_NODES) -> FusedPlan:
    """Segment ``plan``'s post-order into maximal same-engine fusable
    chains.  Always returns a FusedPlan (possibly with no segments — the
    middleware caches it either way so unfusable shapes are analyzed
    once); never raises on an unfusable query."""
    nodes = query.nodes()
    fp = query_fingerprint(query)
    empty = FusedPlan(plan.key, fp, (), injector)
    if len(plan.assignment) != len(nodes):
        return empty
    uids = [n.uid for n in nodes]
    if len(set(uids)) != len(uids):
        # shared subtree: a uid at several positions breaks the positional
        # keying (and a member could gain consumers outside its segment)
        return empty
    pos_of = {uid: pos for pos, uid in enumerate(uids)}
    amap = dict(plan.assignment)         # position -> engine

    def fusable(pos: int, node: PolyOp) -> bool:
        return (node.op != SCOPE_OP and node.op in FUSABLE_OPS
                and amap[pos] in FUSABLE_ENGINES)

    # greedy bottom-up: a fusable node absorbs each fusable same-engine
    # input chain (post-order means input chains are complete when their
    # single consumer — this is a tree — arrives)
    seg_of: Dict[int, int] = {}          # position -> segment id
    members: Dict[int, List[int]] = {}   # segment id -> positions
    next_id = 0
    for pos, node in enumerate(nodes):
        if not fusable(pos, node):
            continue
        sid = next_id
        next_id += 1
        mine = [pos]
        for inp in node.inputs:
            if not isinstance(inp, PolyOp):
                continue
            ip = pos_of[inp.uid]
            isid = seg_of.get(ip)
            if isid is not None and amap[ip] == amap[pos]:
                mine = members.pop(isid) + mine
        members[sid] = mine
        for p in mine:
            seg_of[p] = sid

    segments: List[FusedSegment] = []
    for mine in members.values():
        if len(mine) < min_nodes:
            continue
        mine = sorted(mine)              # ascending post-order = topo order
        segments.append(_build_segment(nodes, pos_of, amap, mine,
                                       query, catalog, cost_model))
    segments.sort(key=lambda s: s.root_pos)
    return FusedPlan(plan.key, fp, tuple(segments), injector)


def _build_segment(nodes, pos_of, amap, mine: List[int], query: PolyOp,
                   catalog, cost_model) -> FusedSegment:
    midx = {p: j for j, p in enumerate(mine)}
    ext_sources: List[Tuple[str, Any]] = []
    ext_slot: Dict[Tuple[str, Any], int] = {}
    specs: List[Tuple[Tuple[str, int], ...]] = []
    for p in mine:
        spec: List[Tuple[str, int]] = []
        for inp in nodes[p].inputs:
            if isinstance(inp, PolyOp) and pos_of[inp.uid] in midx:
                spec.append(("mem", midx[pos_of[inp.uid]]))
                continue
            src = ("ref", inp.name) if isinstance(inp, Ref) \
                else ("pos", pos_of[inp.uid])
            slot = ext_slot.get(src)
            if slot is None:
                slot = ext_slot[src] = len(ext_sources)
                ext_sources.append(src)
            spec.append(("ext", slot))
        specs.append(tuple(spec))
    attrs_list = tuple(tuple(sorted(nodes[p].attrs.items())) for p in mine)
    ops = tuple(nodes[p].op for p in mine)
    engine = amap[mine[0]]
    key = _segment_key(engine, ops, attrs_list, specs, len(ext_sources))
    weights = _segment_weights(query, catalog, cost_model, nodes, mine,
                               engine)
    return FusedSegment(engine, tuple(mine), ops, attrs_list, tuple(specs),
                        tuple(ext_sources), weights, key)


def _segment_key(engine, ops, attrs_list, specs, n_ext) -> str:
    """Structural signature: everything the compiled callable's behavior
    depends on (engine, member ops, EXACT attrs, wiring, ext arity) and
    nothing it does not (shapes/dtypes — ``jax.jit`` specializes on those
    beneath this key, so structurally-identical segments across different
    queries share one callable)."""
    mem = ";".join(
        f"{op}[{','.join(f'{k}={v!r}' for k, v in attrs)}]"
        f"({','.join(f'{kind}{i}' for kind, i in spec)})"
        for op, attrs, spec in zip(ops, attrs_list, specs))
    return f"{engine}:{n_ext}:{mem}"


def _segment_weights(query, catalog, cost_model, nodes, mine,
                     engine) -> Tuple[float, ...]:
    """Pro-rata attribution weights: the executor splits a fused segment's
    measured seconds across member nodes by *predicted* cost, so
    ``per_node_seconds`` keeps feeding the monitor, drift re-planning and
    the per-engine straggler detectors exactly as unfused serves do."""
    if cost_model is None:
        return tuple([1.0 / len(mine)] * len(mine))
    try:
        sizes, _ = estimate_sizes_shapes(query, catalog)
        pred = [max(cost_model.op_seconds(
                    engine, nodes[p].op,
                    _work_elems(nodes[p], sizes, catalog)), 1e-12)
                for p in mine]
    except Exception:                     # never let sizing sink the fuse
        return tuple([1.0 / len(mine)] * len(mine))
    total = sum(pred)
    return tuple(w / total for w in pred)


# ---------------------------------------------------------------------------
# lowering + compilation
# ---------------------------------------------------------------------------

def _lower(op: str, attrs: Dict[str, Any], args, fills, vcs,
           want_aux: bool):
    """One member op as a pure function of jnp arrays — the trace-level
    mirror of ``engines._da_*`` (same math, minus the container wrappers).
    ``fills`` aligns with ``args``: the fill value each argument's
    container carries (select writes it into masked-out slots).  ``vcs``
    also aligns with ``args``: each argument's valid-count as a traced
    scalar, or ``None`` meaning *full* (every element valid — resolve with
    the static ``args[i].size``).  Returns ``(out, vc_out, aux)``:
    ``vc_out`` is the member's output valid-count under the same
    convention (only select narrows it; count's 0-d output is full), and
    ``aux`` is the select mask sum when ``want_aux`` (root selects must
    reproduce the eager engine's ``valid_count`` on the container)."""
    if op == "matmul":
        return jnp.dot(args[0], args[1],
                       precision=jax.lax.Precision.HIGHEST), None, None
    if op == "add":
        return args[0] + args[1], None, None
    if op == "scale":
        return args[0] * attrs["factor"], None, None
    if op == "transpose":
        return args[0].T, None, None
    if op == "select":
        lo = attrs.get("lo", -np.inf)
        hi = attrs.get("hi", np.inf)
        m = (args[0] >= lo) & (args[0] <= hi)
        out = jnp.where(m, args[0], fills[0])
        vc = jnp.sum(m)
        return out, vc, (vc if want_aux else None)
    if op == "count":
        # the eager op is O(1) metadata lookup; here the metadata is the
        # threaded valid-count value (traced for a select upstream or a
        # padded external, static size otherwise)
        vc = vcs[0] if vcs[0] is not None else args[0].size
        return jnp.asarray(vc, jnp.int32), None, None
    if op == "haar":
        from repro.kernels import ops as kops
        return kops.haar(args[0], attrs["levels"]), None, None
    if op == "tfidf":
        from repro.core.engines import tfidf_dense
        return tfidf_dense(args[0]), None, None
    if op == "knn":
        from repro.kernels import ops as kops
        idx, _score = kops.knn(args[0], jnp.atleast_2d(args[1]),
                               attrs["k"])
        return idx, None, None
    raise ValueError(f"op {op!r} is not fusable")


def _build_callable(seg: FusedSegment) -> Callable:
    """The segment as one function ``fn(ext_arrays, ext_fills, ext_vcs) ->
    (root_array, root_aux)``, jitted whole.  Intermediates never leave the
    trace; engine-produced containers carry the default fill (0.0), so
    member-to-member fills are the constant 0.0 while external inputs pass
    their container's real fill in as a traced scalar (no retrace when a
    catalog object's fill differs between serves).  ``ext_vcs`` are the
    external containers' valid-counts, likewise traced scalars: the loop
    threads a per-member valid-count alongside the data (select narrows
    it, count reads it) so metadata-consuming members fuse without
    retracing when only the count changes."""
    ops, attrs_list, specs = seg.ops, seg.attrs_list, seg.input_specs
    last = len(ops) - 1

    def fn(ext, fills, vcs):
        mem: List[Any] = []
        mem_vc: List[Any] = []
        aux = None
        for j, (op, attrs, spec) in enumerate(zip(ops, attrs_list, specs)):
            args, afills, avcs = [], [], []
            for kind, i in spec:
                if kind == "ext":
                    args.append(ext[i])
                    afills.append(fills[i])
                    avcs.append(vcs[i])
                else:
                    args.append(mem[i])
                    afills.append(0.0)
                    avcs.append(mem_vc[i])
            out, vc_out, a = _lower(op, dict(attrs), args, afills, avcs,
                                    want_aux=j == last)
            mem.append(out)
            mem_vc.append(vc_out)
            if j == last:
                aux = a
        return mem[-1], aux

    return jax.jit(fn)


def compiled_segment(seg: FusedSegment) -> Callable:
    """The process-wide compiled callable for a segment key (built once;
    ``jax.jit`` caches per shapes/dtypes beneath it)."""
    with _REGISTRY_LOCK:
        fn = _COMPILED.get(seg.key)
        if fn is None:
            fn = _COMPILED[seg.key] = _build_callable(seg)
        return fn


def run_fused_segment(seg: FusedSegment,
                      ext_objs) -> Tuple[DenseTensor, bool]:
    """Invoke the segment's compiled callable on already-migrated external
    inputs (containers of the engine's kind).  Raises whatever the trace or
    run raises — the executor owns the fallback.  Returns ``(out, cold)``:
    ``cold`` is True when this (key, ext shapes/dtypes) had never completed
    a run, i.e. the call paid trace+compile — the middleware treats such a
    serve as a warm-up and keeps its wall time out of the plan's measured
    mean (and the divergence re-plan trigger it feeds)."""
    fn = compiled_segment(seg)
    ext = tuple(jnp.asarray(o.data) for o in ext_objs)
    fills = tuple(float(getattr(o, "fill", 0.0)) for o in ext_objs)
    # valid-counts ride along as traced scalars (DenseTensor resolves the
    # "full" sentinel at construction, so this is always a real count)
    vcs = tuple(int(getattr(o, "valid_count", o.data.size))
                for o in ext_objs)
    stamp = (seg.key, tuple((a.shape, str(a.dtype)) for a in ext))
    with _REGISTRY_LOCK:
        cold = stamp not in _WARM
    out, aux = fn(ext, fills, vcs)
    with _REGISTRY_LOCK:
        _WARM.add(stamp)
    if aux is not None:
        # root select: adopt the traced mask sum as valid_count — the same
        # (blocking) int() the eager engine op performs
        return DenseTensor(out, valid_count=int(aux)), cold
    return DenseTensor(out), cold
