"""End-to-end observability battery: request span trees (core.tracing),
the process-wide metrics registry (runtime.telemetry), cross-process span
propagation over the procpool pipe RPC, the per-worker-count dispatch
calibration table, and the server's metrics-backed stats view.

The cross-island fixtures mirror test_multi_island_api's canonical query
(RELATIONAL join |> ARRAY matmul) so every span kind shows up: plan,
cache_hit, ivm_patch, engine_op, cast — and over a pool, queue_wait /
worker_dispatch / a worker-rooted request re-attached under the master's
tree."""
import glob
import multiprocessing
import os
import sys
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (BigDAWG, ColumnarTable, DenseTensor, Span, Trace,
                        Tracer, connect)
from repro.core import device, tracing
from repro.core.costmodel import CostModel
from repro.core.executor import (DISPATCH_PROBE_WORKERS, _dispatch_overhead,
                                 execute_plan)
from repro.core.islands import array, relational
from repro.core.procpool import ProcPool
from repro.runtime.fault import WorkerKillInjector
from repro.runtime.server import QueryServer
from repro.runtime.telemetry import (HIST_BOUNDS, Histogram, Metrics,
                                     _metrics_hammer, default_metrics_path,
                                     load_merged)

TEXT_Q = ("RELATIONAL(join(A, B, left_on=key, right_on=key)) "
          "|> ARRAY(matmul(_, W))")


def _cross_island_session(state_path=None, **kwargs):
    rng = np.random.default_rng(0)
    M = rng.normal(size=(8, 6)).astype(np.float32)
    perm = np.array([2, 0, 5, 1, 4, 3])
    W = rng.normal(size=(6, 4)).astype(np.float32)
    ii, kk = np.meshgrid(np.arange(8), np.arange(6), indexing="ij")
    A = ColumnarTable({"i": ii.ravel().astype(np.int32),
                       "key": kk.ravel().astype(np.int32),
                       "value": M.ravel()})
    B = ColumnarTable({"key": np.arange(6, dtype=np.int32),
                       "j": perm.astype(np.int32)})
    s = connect(state_path, **kwargs)
    s.register("A", A, "columnar").register("B", B, "columnar")
    s.register("W", DenseTensor(jnp.asarray(W)), "dense_array")
    return s


def _assert_connected(trace):
    """Every span except the single master root reaches the root via
    parent links — no orphans, one tree."""
    sids = {sp["sid"] for sp in trace.spans}
    roots = [sp for sp in trace.spans if sp["parent"] is None]
    assert len(roots) == 1
    orphans = [sp for sp in trace.spans
               if sp["parent"] is not None and sp["parent"] not in sids]
    assert orphans == []


# ---------------------------------------------------------------------------
# span primitives
# ---------------------------------------------------------------------------

def test_span_tree_basics():
    tr = Tracer(enabled=True)
    t = tr.start()
    with t.root("request", sig="s") as root:
        with root.child("plan") as p:
            p.annotate(plan_key="k")
        root.event("cache_hit", plan_key="k")
        with root.child("fused_segment", engine="dense_array") as seg:
            seg.static_child("cast", seg.t0, 0.25, src="columnar")
    tree = t.tree()
    assert len(tree) == 1 and tree[0]["name"] == "request"
    names = [c["name"] for c in tree[0]["children"]]
    assert names == ["plan", "cache_hit", "fused_segment"]
    seg = tree[0]["children"][2]
    assert seg["children"][0]["name"] == "cast"
    assert seg["children"][0]["seconds"] == 0.25
    assert seg["children"][0]["start"] == seg["start"]
    assert t.find("cache_hit")[0]["seconds"] == 0.0
    # ids embed the pid -> unique across processes
    assert all(sp["sid"].startswith("%x-" % os.getpid()) for sp in t.spans)
    # adopt extends; portable round-trips
    t2 = Trace(trace_id=t.trace_id)
    t2.adopt(tracing.portable(t))
    assert len(t2) == len(t)


def test_span_end_idempotent_and_exception_safe():
    t = Tracer(True).start()
    root = t.root("request")
    with pytest.raises(RuntimeError):
        with root:
            raise RuntimeError("boom")
    root.end()                      # second end is a no-op
    assert len(t) == 1


def test_disabled_tracer_allocates_nothing():
    tr = Tracer(enabled=False)
    assert tr.start() is None and not tr
    # a propagated upstream context forces a trace even when disabled —
    # the worker half of cross-process propagation
    forced = tr.start(("tid-1", "parent-9"))
    assert forced is not None and forced.trace_id == "tid-1"
    root = forced.root("request")
    root.end()
    assert forced.spans[0]["parent"] == "parent-9"


# ---------------------------------------------------------------------------
# warm in-process serve: span-tree shape
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_session():
    s = _cross_island_session(trace=True, explore_budget=0.0)
    s.execute(TEXT_Q, mode="training")
    yield s


def test_warm_cross_island_span_tree(traced_session):
    res = traced_session.execute(TEXT_Q)
    assert res.report.mode == "production"
    t = res.trace
    assert t is not None
    _assert_connected(t)
    tree = t.tree()
    assert tree[0]["name"] == "request"
    child_names = {c["name"] for c in tree[0]["children"]}
    assert {"plan", "cache_hit", "engine_op"} <= child_names
    # engine_op spans ARE the executor's per-node measurements
    eng_sum = sum(sp["seconds"] for sp in t.find("engine_op"))
    per_node = sum(res.report.per_node_seconds.values())
    assert eng_sum == pytest.approx(per_node, rel=1e-6)
    # the cross-island plan casts columnar -> dense at the scope boundary
    casts = t.find("cast")
    assert casts and casts[0]["attrs"]["src"] == "columnar"
    # the request root's wall time covers the report's measured serve
    root = tree[0]
    assert root["seconds"] >= res.seconds * 0.99
    assert t.to_json().startswith("{")


def test_training_trace_nests_engine_ops_under_train(traced_session):
    res = traced_session.execute(
        "RELATIONAL(join(A, B, left_on=key, right_on=key)) "
        "|> ARRAY(count(_))", mode="training")
    t = res.trace
    _assert_connected(t)
    train = t.find("train")
    assert len(train) == 1 and train[0]["attrs"]["plans"] >= 1
    tsid = train[0]["sid"]
    assert all(sp["parent"] == tsid for sp in t.find("engine_op"))


def _assert_nested(spans, slack=1e-6):
    """Every record has a start, and each child lies within its parent's
    [start, start + seconds] (one clock for every process)."""
    by_sid = {sp["sid"]: sp for sp in spans}
    for sp in spans:
        assert isinstance(sp["start"], float) and sp["seconds"] >= 0.0
        parent = by_sid.get(sp["parent"])
        if parent is None:
            continue
        assert parent["start"] - slack <= sp["start"], (parent, sp)
        assert sp["start"] + sp["seconds"] <= \
            parent["start"] + parent["seconds"] + slack, (parent, sp)


@pytest.mark.parametrize("mode", ["training", "production"])
def test_every_record_has_a_start_within_its_parent(traced_session, mode):
    res = traced_session.execute(TEXT_Q, mode=mode)
    spans = res.trace.spans
    assert {"request", "sig_lock_wait", "engine_op", "host_sync"} <= \
        {sp["name"] for sp in spans}
    _assert_nested(spans)


def test_sig_lock_wait_spans_a_held_signature_lock(traced_session):
    """A second serve of a signature whose lock another serve holds
    records the wait as ``sig_lock_wait``, a child of its request."""
    bd = traced_session.bigdawg
    q = traced_session.parse(TEXT_Q)
    lock = bd._sig_lock(traced_session.execute(q).sig)
    out = {}
    with lock:
        t = threading.Thread(target=lambda: out.update(r=bd.execute(q)))
        t.start()
        time.sleep(1.0)
        released = time.perf_counter()
    t.join(60)
    spans = out["r"].trace.spans
    root = [sp for sp in spans if sp["name"] == "request"][0]
    wait = [sp for sp in spans if sp["name"] == "sig_lock_wait"]
    assert len(wait) == 1 and wait[0]["parent"] == root["sid"]
    # it began while the lock was held and ended once it was released
    assert wait[0]["start"] < released <= wait[0]["start"] + wait[0]["seconds"]


@pytest.mark.parametrize("concurrent", [True, False],
                         ids=["levels", "sequential"])
def test_host_sync_once_per_barrier(traced_session, concurrent):
    """Concurrent dispatch blocks once per topological level, sequential
    dispatch once per engine op: each barrier is one ``host_sync``."""
    bd = traced_session.bigdawg
    q = traced_session.parse(TEXT_Q)
    plan = bd.plan_cache[traced_session.execute(q).sig].plan
    t = Tracer(True).start()
    with t.root("request") as root:
        res = execute_plan(q, plan, bd.catalog, concurrent=concurrent,
                           trace=root)
    syncs = t.find("host_sync")
    assert all(sp["parent"] == root.sid for sp in syncs)
    levels = [sp["attrs"]["level"] for sp in syncs]
    if concurrent:
        assert levels == list(range(res.levels))
    else:
        ops = [sp for sp in t.find("engine_op")
               if sp["attrs"]["op"] != "scope"]
        assert len(syncs) == len(ops) >= 2


def test_plan_change_event_on_a_forced_replan():
    """The serve after a re-plan runs the new plan: its trace carries a
    ``plan_change`` event from the old plan to the new, reason ``replan``.
    Serves on an unchanged plan carry none.  One trained plan, and no
    re-plan until the test forces one: timing noise cannot move it."""
    bd = BigDAWG(train_plans=1, replan_factor=float("inf"), trace=True)
    rng = np.random.default_rng(0)
    bd.register("waves", DenseTensor(jnp.asarray(
        rng.normal(size=(32, 64)).astype(np.float32))), engine="dense_array")
    q = array.tfidf(array.haar(
        relational.select("waves", column="value", lo=0.5), levels=2))
    rep = bd.execute(q, mode="training")
    for _ in range(2):
        assert not bd.execute(q, mode="production").trace.find("plan_change")
    bd.replan_factor = BigDAWG.REPLAN_FACTOR
    entry = bd.plan_cache[rep.sig]
    entry.pinned = entry.restored = False
    entry.predicted_s *= 50.0
    # the engine the trained plan did not use becomes the cheap one
    slow = "columnar" if "columnar" in rep.plan_key else "dense_array"
    fast = "dense_array" if slow == "columnar" else "columnar"
    for op in ("select", "haar", "tfidf"):
        bd.cost_model.observe_op(fast, op, 1e6, 1e-4)
        bd.cost_model.observe_op(slow, op, 1e6, 10.0)
    rep2 = bd.execute(q, mode="production")
    assert rep2.replanned and not rep2.trace.find("plan_change")
    rep3 = bd.execute(q, mode="production")
    assert rep3.plan_key != rep2.plan_key
    changes = rep3.trace.find("plan_change")
    assert [c["attrs"] for c in changes] == [
        {"from": rep2.plan_key, "to": rep3.plan_key, "reason": "replan"}]


@pytest.mark.parametrize("numpy_home", [True, False],
                         ids=["numpy", "device"])
def test_h2d_bytes_counts_numpy_inputs(numpy_home):
    """A table registered as numpy is copied to the device by every op that
    reads it: ``Report.h2d_bytes`` and the ``engine_op`` span count its
    bytes; a device-resident table counts 0."""
    x = np.random.default_rng(1).normal(size=(16, 8)).astype(np.float32)
    bd = BigDAWG(train_plans=1, trace=True)
    bd.register("X", DenseTensor(x if numpy_home else jnp.asarray(x)),
                "dense_array")
    q = array.scale("X", factor=2.0)
    bd.execute(q, mode="training")
    rep = bd.execute(q, mode="production")
    want = x.nbytes if numpy_home else 0
    assert rep.h2d_bytes == want
    assert [sp["attrs"]["h2d_bytes"] for sp in rep.trace.find("engine_op")
            ] == [want]


def test_disabled_tracer_reads_no_clock(monkeypatch, traced_session):
    """With tracing off a warm serve reads the clock only for its own
    measurements: from the same places, as often as a traced serve of the
    same plan does, and never in the tracer."""
    bd = traced_session.bigdawg
    q = traced_session.parse(TEXT_Q)
    bd.execute(q)
    real = time.perf_counter
    calls = {}

    def counting():
        code = sys._getframe(1).f_code
        key = (os.path.basename(code.co_filename), code.co_name)
        calls[key] = calls.get(key, 0) + 1
        return real()

    def count(enabled):
        calls.clear()
        bd.tracer.enabled = enabled
        try:
            monkeypatch.setattr(time, "perf_counter", counting)
            bd.execute(q)
        finally:
            monkeypatch.setattr(time, "perf_counter", real)
            bd.tracer.enabled = True
        return dict(calls)

    traced, untraced = count(True), count(False)
    assert any(k[0] == "tracing.py" for k in traced)
    assert untraced == {k: n for k, n in traced.items()
                        if k[0] != "tracing.py"}


def test_trace_off_by_default_and_zero_alloc(monkeypatch):
    s = _cross_island_session()          # no trace= knob
    s.execute(TEXT_Q, mode="training")

    def _no_alloc(*a, **k):
        raise AssertionError("Trace allocated on the disabled path")
    monkeypatch.setattr(tracing.Trace, "__init__", _no_alloc)
    monkeypatch.setattr(tracing.Span, "__init__", _no_alloc)
    res = s.execute(TEXT_Q)              # warm serve: no Trace/Span built
    assert res.trace is None
    assert res.report.mode == "production"


# ---------------------------------------------------------------------------
# histogram quantiles
# ---------------------------------------------------------------------------

def test_histogram_quantiles_track_numpy_percentiles():
    rng = np.random.default_rng(42)
    samples = np.exp(rng.normal(loc=-6.0, scale=1.5, size=4000))
    h = Histogram()
    for v in samples:
        h.observe(float(v))
    ratio = 10.0 ** (1.0 / 8.0)          # one bucket of log-spaced error
    for q in (0.50, 0.95, 0.99):
        exact = float(np.percentile(samples, 100 * q))
        est = h.quantile(q)
        assert exact / ratio <= est <= exact * ratio, (q, est, exact)
    assert h.count == 4000
    assert h.sum == pytest.approx(float(samples.sum()), rel=1e-6)
    assert h.min == pytest.approx(float(samples.min()))
    assert h.max == pytest.approx(float(samples.max()))


def test_histogram_merge_equals_single_stream():
    rng = np.random.default_rng(7)
    samples = rng.uniform(1e-4, 1e-1, size=900)
    whole = Histogram()
    parts = [Histogram() for _ in range(3)]
    for i, v in enumerate(samples):
        whole.observe(float(v))
        parts[i % 3].observe(float(v))
    merged = Histogram.from_blob(parts[0].to_blob())     # blob round-trip
    merged.merge(parts[1])
    merged.merge(parts[2])
    assert merged.counts == whole.counts
    for q in (0.5, 0.95, 0.99):
        assert merged.quantile(q) == whole.quantile(q)
    assert Histogram().quantile(0.99) == 0.0             # empty -> 0
    assert len(HIST_BOUNDS) == 61


# ---------------------------------------------------------------------------
# metrics registry + merge-on-save
# ---------------------------------------------------------------------------

def test_metrics_registry_roundtrip(tmp_path):
    path = str(tmp_path / "m.metrics.json")
    m = Metrics(path)
    m.counter("a")
    m.counter("a", 2.0)
    m.set_counter("b", 7.0)
    m.gauge("g", 0.25)
    m.observe("lat", 0.01)
    assert m.value("a") == 3.0 and m.value("g") == 0.25
    assert m.value("missing", -1.0) == -1.0
    m.save()
    snap = load_merged(path)
    assert snap["counters"]["a"] == 3.0 and snap["counters"]["b"] == 7.0
    assert snap["gauges"]["g"] == 0.25
    assert snap["histograms"]["lat"]["count"] == 1
    assert default_metrics_path("state/monitor.json") \
        == "state/monitor.metrics.json"


def test_metrics_merge_on_save_three_process_hammer(tmp_path):
    """Three spawned processes hammer one metrics file, saving after every
    round.  Merge-on-save keeps sections exact: each private counter lands
    at rounds, the shared counter at writers*rounds, and the merged
    histogram saw every observation — no torn files, no lost increments."""
    path = str(tmp_path / "contended.metrics.json")
    ctx = multiprocessing.get_context("spawn")
    n_procs, rounds = 3, 6
    procs = [ctx.Process(target=_metrics_hammer,
                         args=(path, f"private-{i}", "shared", rounds, i))
             for i in range(n_procs)]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=120)
        assert p.exitcode == 0
    snap = load_merged(path)
    for i in range(n_procs):
        assert snap["counters"][f"private-{i}"] == rounds
    assert snap["counters"]["shared"] == n_procs * rounds
    assert snap["histograms"]["hammer.latency"]["count"] == n_procs * rounds
    assert snap["gauges"]["hammer.last_round"] == rounds - 1


def test_metrics_snapshot_merges_other_writers(tmp_path):
    path = str(tmp_path / "m.metrics.json")
    a, b = Metrics(path, shared=True), Metrics(path, shared=True)
    a.counter("hits", 2.0)
    b.counter("hits", 5.0)
    a.save()
    b.save()
    # local reads stay per-writer; merged folds the other section in
    assert a.value("hits") == 2.0
    assert a.snapshot()["counters"]["hits"] == 2.0
    assert a.snapshot(merged=True)["counters"]["hits"] == 7.0
    assert b.snapshot(merged=True)["counters"]["hits"] == 7.0


# ---------------------------------------------------------------------------
# serving stack: stats view + session metrics
# ---------------------------------------------------------------------------

def test_queryserver_stats_is_metrics_backed_mapping():
    s = _cross_island_session()
    srv = QueryServer(s.bigdawg)
    assert srv.metrics is s.bigdawg.metrics      # one registry, one lock
    q = s.parse(TEXT_Q)
    srv.submit(q)
    srv.submit(q)
    assert srv.stats["requests"] == 2
    assert srv.stats["trainings"] == 1
    assert srv.stats["cache_hits"] >= 1
    assert isinstance(srv.stats["seconds"], float)
    d = dict(srv.stats)                          # Mapping protocol
    assert d["requests"] == 2 and "breaker_trips" in d
    assert srv.stats() == d                      # callable snapshot
    assert len(srv.stats) == len(d)
    with pytest.raises(KeyError):
        srv.stats["nope"]
    hist = srv.metrics.histogram("server.latency")
    assert hist is not None and hist.count == 2


def test_session_metrics_snapshot():
    s = _cross_island_session()
    s.execute(TEXT_Q, mode="training")
    s.execute(TEXT_Q)
    snap = s.metrics()
    assert snap["counters"]["bd.serve_seconds"] > 0.0
    assert snap["histograms"]["bd.serve_latency"]["count"] >= 1
    assert snap["histograms"]["bd.serve_latency"]["p50"] > 0.0


# ---------------------------------------------------------------------------
# dispatch-overhead calibration table (per worker count)
# ---------------------------------------------------------------------------

def test_dispatch_table_interpolates_and_persists(tmp_path):
    cm = CostModel()
    cm.observe_dispatch(1e-4, workers=1)
    cm.observe_dispatch(3e-4, workers=4)
    assert cm.dispatch_overhead_s(1) == pytest.approx(1e-4)
    assert cm.dispatch_overhead_s(4) == pytest.approx(3e-4)
    # linear interpolation between bracketing probes
    assert cm.dispatch_overhead_s(2) == pytest.approx(1e-4 + (3e-4 - 1e-4) / 3)
    # flat extrapolation outside the probed range
    assert cm.dispatch_overhead_s(8) == pytest.approx(3e-4)
    assert cm.dispatch_overhead_s(0) == pytest.approx(1e-4)
    path = str(tmp_path / "calibration.json")
    cm.save(path)
    cm2 = CostModel()
    cm2.load(path)
    assert set(cm2.dispatch_table) == {1, 4}
    assert cm2.dispatch_overhead_s(2) == pytest.approx(cm.dispatch_overhead_s(2))
    # legacy single-point mean still feeds old readers
    assert cm2.dispatch_overhead.n == 2


def test_dispatch_probe_measures_each_worker_count():
    cm = CostModel()
    got = _dispatch_overhead(cm, workers=2)
    assert got > 0.0
    assert set(cm.dispatch_table) >= set(DISPATCH_PROBE_WORKERS)
    for w in DISPATCH_PROBE_WORKERS:
        assert cm.dispatch_table[w].mean > 0.0
    # the probe ran once; later calls reuse the calibrated table
    assert _dispatch_overhead(cm, workers=2) == pytest.approx(got)


# ---------------------------------------------------------------------------
# cross-process propagation over the procpool
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_pool(tmp_path_factory):
    rng = np.random.RandomState(11)
    state = str(tmp_path_factory.mktemp("obsstate") / "monitor.json")
    p = ProcPool(2, state_path=state, train_plans=2, trace=True,
                 request_timeout_s=120.0)
    p.register("M", DenseTensor(rng.rand(40, 3)), "dense_array", shards=2)
    p.register("W", DenseTensor(rng.rand(3, 4)), "dense_array")
    yield p
    p.close()


def test_pool_trace_spans_one_connected_tree(traced_pool):
    q = array.matmul("M", "W")
    traced_pool.execute(q, mode="training")
    rep = traced_pool.execute(q)
    assert rep.mode == "production"
    t = rep.trace
    assert t is not None
    _assert_connected(t)
    # spans from two processes share one trace id and link up: master root
    # (queue_wait/worker_dispatch) + the worker's re-attached request
    pids = {sp["sid"].split("-")[0] for sp in t.spans}
    assert len(pids) >= 2
    master_pid = "%x" % os.getpid()
    assert master_pid in pids
    wroots = [sp for sp in t.spans
              if sp["name"] == "request" and sp["parent"] is not None]
    assert len(wroots) >= 1
    assert all(not sp["sid"].startswith(master_pid + "-") for sp in wroots)
    assert t.find("worker_dispatch") and t.find("queue_wait")
    assert t.find("engine_op")
    # per-span seconds are consistent with the Report's measured wall time:
    # the worker's request span covers the serve (the hard invariant), and
    # doesn't wildly exceed it — the span also wraps middleware bookkeeping
    # (signature hashing, cache lookup, monitor reads) outside the
    # executor-timed rep.seconds, which on a loaded 1-CPU host can cost
    # tens of ms, so the upper bound is a loose sanity check only
    wall = max(sp["seconds"] for sp in wroots)
    assert wall >= rep.seconds * 0.99
    assert wall - rep.seconds <= max(0.10 * wall, 0.5)


def test_pool_trace_survives_worker_kill_and_respawn():
    """A worker killed mid-dispatch respawns and the retried request still
    comes back with one connected trace: the respawn shows up as an event
    under the master root, and the surviving worker's spans re-attach."""
    rng = np.random.RandomState(3)
    inj = WorkerKillInjector(kill_on_dispatch=2)
    p = ProcPool(2, train_plans=2, retries=1, kill_injector=inj,
                 trace=True, request_timeout_s=120.0)
    try:
        p.register("M", DenseTensor(rng.rand(40, 3)), "dense_array")
        p.register("W", DenseTensor(rng.rand(3, 4)), "dense_array")
        q = array.matmul("M", "W")
        p.execute(q, mode="training")              # dispatch 1: survives
        rep = p.execute(q, mode="training")        # dispatch 2: kill lands
        assert inj.kills == 1 and p.respawns >= 1
        t = rep.trace
        assert t is not None
        _assert_connected(t)
        assert len(t.find("respawn")) >= 1
        assert t.find("engine_op")                 # retried serve's spans
        assert len(t.find("request")) == 2         # master root + worker
        # respawns surfaced through the metrics registry too
        assert p.metrics.value("pool.respawns") >= 1
    finally:
        p.close()


def test_pool_scatter_trace_collects_all_shards():
    rng = np.random.RandomState(5)
    p = ProcPool(2, train_plans=2, scatter="always", trace=True,
                 request_timeout_s=120.0)
    try:
        p.register("M", DenseTensor(rng.rand(40, 3)), "dense_array",
                   shards=2)
        q = array.count("M")
        p.execute(q, mode="training")
        rep = p.execute(q)
        assert rep.shards == 2
        t = rep.trace
        _assert_connected(t)
        wroots = [sp for sp in t.spans
                  if sp["name"] == "request" and sp["parent"] is not None]
        assert len(wroots) == rep.shards           # one subtree per shard
        assert len(t.find("gather_fold")) >= rep.shards - 1
        assert p.metrics.value("pool.scatter_serves") >= 1
    finally:
        p.close()


def test_pool_profiles_and_memory_from_inside_its_workers(traced_pool,
                                                         tmp_path):
    """``ProcPool.profile`` has each worker trace its own device into its
    own directory; a traced request served meanwhile carries the worker's
    ``clock_anchor``, whose annotation is in that worker's profile; and
    ``memory_stats`` answers for every worker."""
    from jax.profiler import ProfileData
    q = array.matmul("M", "W")
    traced_pool.execute(q, mode="training")
    with traced_pool.profile(str(tmp_path)) as dirs:
        rep = traced_pool.execute(q)
    assert dirs == [str(tmp_path / f"worker{i}") for i in range(2)]
    anchors = rep.trace.find("clock_anchor")
    assert len(anchors) == 1
    pid = anchors[0]["sid"].split("-")[0]
    found = []
    for d in dirs:
        for f in glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True):
            for plane in ProfileData.from_file(f).planes:
                for line in plane.lines:
                    found += [dict(e.stats).get("request")
                              for e in line.events if e.name == device.ANCHOR]
    assert found == ["r" + anchors[0]["parent"]]
    assert pid != "%x" % os.getpid()
    stats = traced_pool.memory_stats()
    assert len(stats) == 2 and all(isinstance(s, dict) for s in stats)


def test_pool_metrics_persist_merges_workers(traced_pool):
    q = array.matmul("M", "W")
    traced_pool.execute(q)
    traced_pool.persist()
    path = default_metrics_path(traced_pool.state_path)
    snap = load_merged(path)
    assert snap["counters"]["pool.dispatches"] >= 1
    assert snap["counters"]["bd.serve_seconds"] > 0.0
