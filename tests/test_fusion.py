"""Plan-level kernel fusion (core/fuseplan.py): the differential property
battery ISSUE 8 demands.

The contract under test: fusing a cached plan's same-engine chains into
single jitted callables must be *unobservable* except in speed — identical
values, shapes, valid counts and island roll-ups across every fusable op
family, chain length and input data model; segmentation must never cross an
engine or island (scope) boundary; a fused segment that fails to
trace/compile falls back to node-by-node execution (sticky per signature)
without changing results; and the monitor/drift loop keeps working on
pro-rata attributed timings.
"""
import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from repro.proptest import given, settings, strategies as st

from repro.core import fuseplan
from repro.core.executor import execute_plan
from repro.core.fuseplan import (FUSABLE_ENGINES, FUSABLE_OPS, fuse_plan,
                                 query_fingerprint)
from repro.core.islands import array, relational, scope
from repro.core.middleware import BigDAWG
from repro.core.ops import SCOPE_OP, PolyOp, Ref
from repro.core.planner import Plan
from repro.core.tables import DenseTensor
from repro.runtime.fault import FusionFaultInjector
from repro.runtime.server import QueryServer

N, T = 8, 16          # base shape; transpose flips it to (16, 8)


@pytest.fixture(autouse=True)
def _fresh_fusion_registry():
    """The compiled-callable cache is process-wide and the broken-key marks
    are sticky by design — isolate every test from its neighbors."""
    fuseplan.reset_cache()
    yield
    fuseplan.reset_cache()


# the registered tables and their homes, drawn in this order
_TABLES = (("Xd", (N, T), "dense_array"), ("Xc", (N, T), "columnar"),
           ("Xs", (N, T), "kv_sparse"), ("W16", (16, 16), "dense_array"),
           ("W8", (8, 8), "dense_array"), ("B816", (8, 16), "dense_array"),
           ("B168", (16, 8), "dense_array"), ("Q16", (4, 16), "dense_array"),
           ("Q8", (4, 8), "dense_array"))


def _data():
    rng = np.random.default_rng(7)
    return {name: rng.normal(size=shape).astype(np.float32)
            for name, shape, _ in _TABLES}


def _middleware(**kw):
    bd = BigDAWG(train_plans=2, train_repeats=1, **kw)
    data = _data()
    for name, _, engine in _TABLES:
        bd.register(name, DenseTensor(jnp.asarray(data[name])), engine)
    return bd


# ---------------------------------------------------------------------------
# the 200-example differential property: fused == unfused
# ---------------------------------------------------------------------------

@st.composite
def chain_specs(draw):
    """One random fusable chain: the input's home data model plus 1-5 ops,
    each drawn from whatever is shape-legal at that point.  Attr values are
    binned to small sets so the 200 examples revisit compiled segment
    signatures instead of paying 400 fresh traces."""
    n_ops = draw(st.integers(min_value=1, max_value=5))
    src = draw(st.sampled_from(["Xd", "Xc", "Xs"]))
    shape = (N, T)
    ops = []
    for i in range(n_ops):
        choices = ["select", "scale", "tfidf", "add", "matmul", "transpose",
                   "haar"]          # both shapes keep cols % 4 == 0
        if i == n_ops - 1:
            choices.append("knn")   # int indices: terminal only
            choices.append("count")  # 0-d scalar: terminal only
        op = draw(st.sampled_from(choices))
        if op == "select":
            ops.append(("select",
                        {"lo": draw(st.sampled_from([-0.5, 0.0, 0.5]))}))
        elif op == "scale":
            ops.append(("scale",
                        {"factor": draw(st.sampled_from([0.5, 2.0]))}))
        elif op == "tfidf":
            ops.append(("tfidf", {}))
        elif op == "haar":
            ops.append(("haar",
                        {"levels": draw(st.sampled_from([1, 2]))}))
        elif op == "transpose":
            ops.append(("transpose", {}))
            shape = (shape[1], shape[0])
        elif op == "add":
            ops.append(("add",
                        {"other": "B816" if shape == (N, T) else "B168"}))
        elif op == "matmul":
            ops.append(("matmul",
                        {"other": "W16" if shape[1] == 16 else "W8"}))
        elif op == "knn":
            ops.append(("knn",
                        {"other": "Q16" if shape[1] == 16 else "Q8",
                         "k": 3}))
            break
        elif op == "count":
            ops.append(("count", {}))
            break
    return src, tuple(ops)


EPS32 = float(np.finfo(np.float32).eps)


def _haar64(x, err, levels):
    """One Haar level at a time, in float64, with the float32 error bound:
    each output sums two terms (one rounding) and is scaled by 1/sqrt(2),
    itself rounded to float32 (two more)."""
    inv = 1.0 / np.sqrt(2.0)
    a, e_a, det, e_det = x, err, [], []
    for _ in range(levels):
        ev, od, e_ev, e_od = a[:, 0::2], a[:, 1::2], e_a[:, 0::2], \
            e_a[:, 1::2]
        prop = inv * (e_ev + e_od)
        new = 3 * EPS32 * inv * (np.abs(ev) + np.abs(od) + e_ev + e_od)
        det.append((ev - od) * inv)
        e_det.append(prop + new)
        a, e_a = (ev + od) * inv, prop + new
    return (np.concatenate([a] + det[::-1], axis=1),
            np.concatenate([e_a] + e_det[::-1], axis=1))


def _tfidf64(tf, err):
    """``tfidf_dense`` in float64 with its float32 error bound: the input's
    error through the weights and the row norm, the norm's 2T roundings (T
    products, T additions, any order) and a few more per weight, and where
    an input is within its error of 0, the document frequency either way."""
    n, t = tf.shape

    def weights(df):
        w = tf * (np.log(n / (1.0 + df)) + 1.0)
        return w / np.maximum(np.linalg.norm(w, axis=1, keepdims=True), 1e-9)

    pos, unsure = tf > 0, (np.abs(tf) <= err) & (err > 0)
    lo, hi = weights(np.sum(pos & ~unsure, 0)), weights(np.sum(pos | unsure,
                                                              0))
    df = np.sum(pos, 0)
    w = weights(df)
    idf = np.log(n / (1.0 + df)) + 1.0
    norm = np.maximum(np.linalg.norm(tf * idf, axis=1, keepdims=True), 1e-9)
    prop = (err * idf + np.abs(w) * (err @ idf)[:, None]) / norm
    return w, prop + (2 * t + 8) * EPS32 * np.abs(w) + np.abs(hi - lo)


def _chain64(src, ops):
    """The float32 chain in float64 numpy, and a bound on how far any
    float32 evaluation of it can lie from that: per element, the error each
    op brings in its own rounding plus the error of its inputs carried
    through it.  A matmul over K terms adds K * eps times the magnitude of
    its terms (|x| @ |W|), in whatever order the K are summed, so fused and
    unfused programs, which only reassociate sums, both fall within it.
    None for an integer result (knn's indices, count)."""
    data = {k: v.astype(np.float64) for k, v in _data().items()}
    x, err = data[src], np.zeros_like(data[src])
    for op, a in ops:
        if op == "select":
            keep = x >= a["lo"]
            unsure = np.abs(x - a["lo"]) <= err
            x, err = np.where(keep, x, 0.0), np.where(
                unsure, np.abs(x) + err, np.where(keep, err, 0.0))
        elif op == "scale":
            x, err = x * a["factor"], abs(a["factor"]) * err \
                + EPS32 * np.abs(x * a["factor"])
        elif op == "add":
            o = data[a["other"]]
            err = err + EPS32 * (np.abs(x) + np.abs(o) + err)
            x = x + o
        elif op == "matmul":
            w = data[a["other"]]
            err = err @ np.abs(w) + w.shape[0] * EPS32 * (
                (np.abs(x) + err) @ np.abs(w))
            x = x @ w
        elif op == "transpose":
            x, err = x.T, err.T
        elif op == "haar":
            x, err = _haar64(x, err, a["levels"])
        elif op == "tfidf":
            x, err = _tfidf64(x, err)
        else:                            # knn, count: integer results
            return None
    return x, err


def _build_query(src, ops):
    node = Ref(src)
    for op, a in ops:
        if "other" in a:
            attrs = {k: v for k, v in a.items() if k != "other"}
            node = array._build(op, node, Ref(a["other"]), **attrs)
        else:
            node = array._build(op, node, **a)
    return node


_BD = None


def _shared_bd():
    global _BD
    if _BD is None:
        _BD = _middleware()
    return _BD


@settings(max_examples=200, deadline=None)
@given(chain_specs())
def test_fused_equals_unfused(spec):
    src, ops = spec
    bd = _shared_bd()
    query = _build_query(src, ops)
    nodes = query.nodes()
    plan = Plan(tuple((i, "dense_array") for i in range(len(nodes))))
    fused = fuse_plan(query, plan, bd.catalog, cost_model=bd.cost_model)
    base = execute_plan(query, plan, bd.catalog, concurrent=True)
    got = execute_plan(query, plan, bd.catalog, concurrent=True, fused=fused)
    assert got.fusion_fallbacks == 0, fuseplan.broken_keys()
    if len(ops) >= fuseplan.MIN_SEGMENT_NODES:
        assert got.fused_segments, (src, ops)     # the chain really fused
    else:
        assert not got.fused_segments             # 1-node chains never do
    assert base.value.data.shape == got.value.data.shape
    ref = _chain64(src, ops)
    if ref is None:
        np.testing.assert_array_equal(np.asarray(base.value.data),
                                      np.asarray(got.value.data))
    else:
        # both within the float32 bound of the float64 value, so within
        # twice it of each other
        want, bound = ref
        for out in (base, got):
            off = np.abs(np.asarray(out.value.data, np.float64) - want)
            assert np.all(off <= bound), (off.max(), bound[off > bound])
    assert base.value.valid_count == got.value.valid_count
    # pro-rata attribution: every fused member got a share of the segment
    for seg in got.fused_segments:
        for pos in seg:
            assert got.per_node_seconds[nodes[pos].uid] >= 0.0


def test_fused_count_reads_threaded_valid_count():
    """``count`` fuses by consuming the valid-count value threaded through
    the trace: a padded external's metadata count enters as a traced
    scalar, and an upstream select's mask sum replaces it — both must match
    the eager engine exactly, with count mid-chain as well as at the
    root."""
    bd = _middleware()
    rng = np.random.default_rng(11)
    padded = DenseTensor(jnp.asarray(rng.normal(size=(N, T))
                                     .astype(np.float32)), valid_count=29)
    bd.register("Xpad", padded, "dense_array")
    queries = [
        array.count(array.select(Ref("Xd"), lo=0.0, hi=0.7)),
        array.count(array.scale(Ref("Xpad"), factor=2.0)),
        array.scale(array.count(array.select(Ref("Xd"), lo=-0.3)),
                    factor=0.5),
        array.count(array.select(array.matmul(Ref("Xd"), Ref("W16")),
                                 lo=0.0)),
    ]
    for query in queries:
        nodes = query.nodes()
        plan = Plan(tuple((i, "dense_array") for i in range(len(nodes))))
        fused = fuse_plan(query, plan, bd.catalog, cost_model=bd.cost_model)
        assert any("count" in s.ops for s in fused.segments)
        base = execute_plan(query, plan, bd.catalog, concurrent=True)
        got = execute_plan(query, plan, bd.catalog, concurrent=True,
                           fused=fused)
        assert got.fusion_fallbacks == 0, fuseplan.broken_keys()
        assert got.fused_segments
        np.testing.assert_array_equal(np.asarray(base.value.data),
                                      np.asarray(got.value.data))
        assert base.value.valid_count == got.value.valid_count
    # the metadata really flowed: a padded count is 29, not N*T
    q = array.count(array.transpose(array.transpose(Ref("Xpad"))))
    plan = Plan(((0, "dense_array"), (1, "dense_array"),
                 (2, "dense_array")))
    base = execute_plan(q, plan, bd.catalog, concurrent=True)
    # NB the eager transpose drops padding metadata (engine outputs are
    # full) — fused must mirror that, not "fix" it
    got = execute_plan(q, plan, bd.catalog, concurrent=True,
                       fused=fuse_plan(q, plan, bd.catalog))
    assert int(np.asarray(got.value.data)) == \
        int(np.asarray(base.value.data))


# ---------------------------------------------------------------------------
# segmentation never crosses an engine or island (scope) boundary
# ---------------------------------------------------------------------------

def _boundary_query():
    """A cross-island shape with an explicit SCOPE seam in the middle and
    fusable ops on both sides of it."""
    left = relational.select(Ref("Xc"), lo=0.0)
    mid = scope(array, relational.matmul(left, Ref("W16")))
    return array.scale(array.haar(mid, levels=2), factor=2.0)


@settings(max_examples=100, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_segments_never_cross_engine_or_scope_boundaries(seed):
    bd = _shared_bd()
    query = _boundary_query()
    nodes = query.nodes()
    rng = np.random.default_rng(seed)
    assignment = []
    for pos, node in enumerate(nodes):
        if node.op == SCOPE_OP:
            assignment.append((pos, "dense_array"))   # array-model boundary
        else:
            assignment.append(
                (pos, str(rng.choice(["dense_array", "columnar"]))))
    plan = Plan(tuple(assignment))
    amap = dict(assignment)
    fused = fuse_plan(query, plan, bd.catalog)
    pos_of = {n.uid: p for p, n in enumerate(nodes)}
    seen = set()
    for seg in fused.segments:
        assert len(seg.positions) >= fuseplan.MIN_SEGMENT_NODES
        assert seg.engine in FUSABLE_ENGINES
        for pos in seg.positions:
            assert pos not in seen            # segments are disjoint
            seen.add(pos)
            node = nodes[pos]
            assert node.op != SCOPE_OP        # island seams stay explicit
            assert node.op in FUSABLE_OPS
            assert amap[pos] == seg.engine    # one engine per segment
        # connectivity: every non-root member's consumer is IN the segment,
        # so a chain interrupted by a scope node (or a foreign-engine node)
        # can never contribute both of its sides to one segment
        member = set(seg.positions)
        for pos in seg.positions[:-1]:
            consumer = next(p for p, n in enumerate(nodes)
                            if any(isinstance(i, PolyOp)
                                   and pos_of[i.uid] == pos
                                   for i in n.inputs))
            assert consumer in member


def test_shared_subtree_is_never_fused():
    bd = _shared_bd()
    shared = array.haar(Ref("Xd"), levels=2)
    query = array.add(shared, shared)          # one uid, two positions
    plan = Plan(tuple((i, "dense_array")
                      for i in range(len(query.nodes()))))
    assert fuse_plan(query, plan, bd.catalog).segments == ()


def test_fingerprint_distinguishes_binned_constants():
    q1 = array.scale(array.haar(Ref("Xd"), levels=2), factor=2.0)
    q2 = array.scale(array.haar(Ref("Xd"), levels=2), factor=0.5)
    assert query_fingerprint(q1) != query_fingerprint(q2)


# ---------------------------------------------------------------------------
# middleware/session surface: fuse knob, Result/stats reporting
# ---------------------------------------------------------------------------

def _pipeline_query():
    """A 4-op chain of dense_array-ONLY ops: every plan the DP (or a replan)
    can produce is the all-dense one, so these middleware-level tests are
    deterministic even when the first jit-cold fused serve triggers the
    online re-planner.  Mixed-candidate ops (select/haar/tfidf) get their
    fused-vs-unfused coverage from the 200-example property above."""
    x = array.transpose(array.transpose(Ref("Xd")))
    return array.scale(array.add(x, Ref("B816")), factor=2.0)


def test_fuse_knob_end_to_end():
    bd_on = _middleware(fuse=True)
    bd_off = _middleware(fuse=False)
    q = _pipeline_query()
    t_on = bd_on.execute(q, mode="training")
    t_off = bd_off.execute(q, mode="training")
    assert t_on.fused_segments == ()           # training always unfused
    p_on = bd_on.execute(q, mode="production")
    p_off = bd_off.execute(q, mode="production")
    assert p_on.fused_segments and not p_off.fused_segments
    np.testing.assert_allclose(np.asarray(p_on.result.data),
                               np.asarray(p_off.result.data),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(t_on.result.data),
                               np.asarray(p_on.result.data),
                               rtol=1e-5, atol=1e-5)
    assert bd_on.fused_serves == 1 and bd_on.fusion_segments >= 1
    assert bd_off.fused_serves == 0


def test_session_result_surfaces_fusion_and_islands():
    from repro.core.api import Session
    q = relational.select(Ref("Xc"), column="value", lo=0.0)
    # the fused tail uses dense_array-ONLY ops (scale/transpose), so the DP
    # cannot plan it apart — the segment is guaranteed whatever it learns
    q = array.scale(array.transpose(array.transpose(scope(array, q))),
                    factor=0.5)
    res = {}
    for fuse in (True, False):
        s = Session(_middleware(fuse=fuse))
        s.execute(q)                           # training
        res[fuse] = s.execute(q)               # production
    assert res[True].fused_segments and not res[False].fused_segments
    assert res[True].islands == res[False].islands
    np.testing.assert_allclose(np.asarray(res[True].value.data),
                               np.asarray(res[False].value.data),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fallback fault injection: serve completes unfused, sticky, counted
# ---------------------------------------------------------------------------

def test_fusion_fallback_is_sticky_and_counted():
    inj = FusionFaultInjector()
    bd = _middleware(fusion_injector=inj)
    srv = QueryServer(bd)
    q = _pipeline_query()
    srv.submit(q)                              # training
    r1 = srv.submit(q)                         # warm fused serve
    assert r1.fused_segments and r1.fusion_fallbacks == 0
    inj.arm(1)                                 # next fused call "refuses
    r2 = srv.submit(q)                         # to trace" mid-serve
    assert r2.fusion_fallbacks == 1
    assert r2.fused_segments == ()
    np.testing.assert_allclose(np.asarray(r2.result.data),
                               np.asarray(r1.result.data),
                               rtol=1e-5, atol=1e-5)
    assert len(inj.fired) == 1
    assert fuseplan.is_broken(inj.fired[0])
    r3 = srv.submit(q)                         # sticky: no retry, no new
    assert r3.fusion_fallbacks == 0            # fallback transition
    assert r3.fused_segments == ()
    np.testing.assert_allclose(np.asarray(r3.result.data),
                               np.asarray(r1.result.data),
                               rtol=1e-5, atol=1e-5)
    assert len(inj.fired) == 1                 # fused path never re-entered
    assert srv.stats["fusion_fallbacks"] == 1
    assert srv.stats["fused_serves"] == 1


def test_fused_kernel_failure_propagates_instead_of_falling_back(
        monkeypatch):
    """Only a trace refusal re-runs a segment unfused: a compile, kernel or
    device failure of the fused call reaches the caller, unmarked."""
    bd = _middleware()
    srv = QueryServer(bd)
    q = _pipeline_query()
    srv.submit(q)                              # training
    assert srv.submit(q).fused_segments        # warm fused serve

    def refused_kernel(seg, ext_objs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(fuseplan, "run_fused_segment", refused_kernel)
    with pytest.raises(RuntimeError, match="Mosaic"):
        srv.submit(q)
    assert fuseplan.broken_keys() == {}
    assert srv.stats["fusion_fallbacks"] == 0


# ---------------------------------------------------------------------------
# monitor attribution: fused serves keep the adaptive loop honest
# ---------------------------------------------------------------------------

def test_fused_serves_do_not_pollute_op_rates_and_drift_still_replans():
    bd = _middleware()
    q = _pipeline_query()
    rep_t = bd.execute(q, mode="training")
    sig = rep_t.sig
    # op-rate snapshot: production serves (fused or not) must never feed the
    # calibrated throughputs — only sequential training runs do
    probe = [("dense_array", op, 4096.0)
             for op in ("transpose", "add", "scale")]
    before = [bd.cost_model.op_seconds(*p) for p in probe]
    n_pos = len(q.nodes())
    for _ in range(3):
        rep = bd.execute(q, mode="production")
        assert rep.fused_segments
        # pro-rata attribution covers EVERY position, like an unfused serve
        assert set(rep.per_node_seconds) == set(range(n_pos))
        assert all(v >= 0.0 for v in rep.per_node_seconds.values())
    after = [bd.cost_model.op_seconds(*p) for p in probe]
    assert before == after
    # drift re-planning still fires on divergence measured from fused serves
    entry = bd.plan_cache[sig]
    entry.predicted_s = max(entry.predicted_s, 1e-4) * 1e3
    entry.restored = False
    rep = bd.execute(q, mode="production")
    assert rep.replanned
    assert bd.replans >= 1


def test_jit_cold_fused_serve_is_a_warmup_not_a_measurement():
    """The FIRST fused serve of a segment signature pays trace+compile: its
    wall time must stay out of the plan's measured mean and must never trip
    the divergence re-planner (which would silently dethrone the incumbent
    plan — observed as a resilience-test failure: failing the incumbent's
    engines no longer degraded the next serve)."""
    bd = _middleware()
    q = _pipeline_query()
    rep_t = bd.execute(q, mode="training")
    n_before = bd.monitor.known_plans(rep_t.sig)[rep_t.plan_key].n
    cold = bd.execute(q, mode="production")    # jit-cold fused serve
    assert cold.fused_segments and not cold.replanned
    assert bd.replans == 0
    assert bd.monitor.known_plans(rep_t.sig)[rep_t.plan_key].n == n_before
    warm = bd.execute(q, mode="production")    # warm serves DO measure
    assert warm.fused_segments
    assert bd.monitor.known_plans(rep_t.sig)[rep_t.plan_key].n == n_before + 1


def test_fused_serve_feeds_health_per_engine():
    from repro.core.health import EngineHealth
    health = EngineHealth()
    bd = _middleware(health=health)
    q = _pipeline_query()
    bd.execute(q, mode="training")
    rep = bd.execute(q, mode="production")
    assert rep.fused_segments and rep.status == "ok"
    # the straggler channel consumed per-engine seconds from the fused serve
    det = health._stragglers.get("dense_array")
    assert det is not None and det.n > 0
