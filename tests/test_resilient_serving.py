"""Resilient serving (ISSUE 6): per-engine circuit breakers, failover
re-planning under an engine mask, the error taxonomy behind the async
Session API, adaptive latency-keyed shedding, and the qlang SQL surface.

Covers the tentpole's contract end to end: breaker state transitions
(closed -> open -> half-open probe -> closed), masked-DP agreement with the
exhaustive enumerator, an injected mid-serve outage that fails over with
ZERO failed requests, and recovery restoring the pre-failure incumbent plan
verbatim (masked serves never pollute the unmasked signature's history).
"""
import json
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (BigDAWG, ColumnarTable, DenseTensor, array, connect,
                        relational, signature)
from repro.core.errors import (BigDAWGError, EngineDown, Overloaded,
                               PlanInfeasible, QueryParseError,
                               is_engine_failure)
from repro.core.health import (CLOSED, DEFAULT_ALWAYS_UP, HALF_OPEN, OPEN,
                               CircuitBreaker, EngineHealth)
from repro.core.middleware import MASK_SEP, _plan_from_key, masked_sig
from repro.core.planner import dp_plans, exhaustive_plans, node_candidates
from repro.core.qlang import bigdawg as parse_text
from repro.runtime.fault import EngineFaultInjector, SimulatedFailure
from repro.runtime.server import BatchServer, QueryServer, Request, Shed


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _fake_clock():
    t = [0.0]
    return t, (lambda: t[0])


def _portable_query():
    """Every node has >= 2 candidate engines (haar: dense/columnar/stream,
    tfidf: dense/columnar/kv_sparse) — failover can always re-plan it."""
    return array.tfidf(array.haar("waves", levels=2))


def _resilient_session(threshold=2, cooldown=5.0, train_plans=2, **kw):
    t, clock = _fake_clock()
    inj = EngineFaultInjector()
    health = EngineHealth(failure_threshold=threshold, cooldown_s=cooldown,
                          time_fn=clock, injector=inj)
    s = connect(health=health, train_plans=train_plans, train_repeats=1,
                **kw)
    rng = np.random.default_rng(0)
    s.register("waves", DenseTensor(jnp.asarray(
        rng.normal(size=(16, 64)).astype(np.float32))), "dense_array")
    s.register("T", ColumnarTable(
        {"v": rng.normal(size=32).astype(np.float32)}), "columnar")
    return s, health, inj, t


# ---------------------------------------------------------------------------
# (1) CircuitBreaker state machine
# ---------------------------------------------------------------------------

def test_breaker_trips_after_threshold_consecutive_failures():
    br = CircuitBreaker("kv_sparse", failure_threshold=3)
    assert br.on_failure(0.0) is False
    assert br.on_failure(0.0) is False
    assert br.state == CLOSED
    assert br.on_failure(1.0) is True          # third consecutive -> OPEN
    assert br.state == OPEN and br.trips == 1 and br.opened_at == 1.0


def test_breaker_success_resets_consecutive_run():
    br = CircuitBreaker("kv_sparse", failure_threshold=2)
    br.on_failure(0.0)
    br.on_success()                            # run broken: back to zero
    assert br.consecutive_failures == 0
    br.on_failure(0.0)
    assert br.state == CLOSED                  # 1 < threshold again


def test_breaker_cooldown_half_open_then_probe_success_closes():
    br = CircuitBreaker("stream", failure_threshold=1, cooldown_s=5.0)
    br.on_failure(0.0)
    assert br.poll(4.9) == OPEN                # cooldown not elapsed
    assert br.poll(5.0) == HALF_OPEN
    br.on_success()                            # the probe came back healthy
    assert br.state == CLOSED and br.trips == 1


def test_breaker_probe_failure_reopens_immediately():
    br = CircuitBreaker("stream", failure_threshold=3, cooldown_s=5.0)
    for _ in range(3):
        br.on_failure(0.0)
    br.poll(6.0)
    assert br.state == HALF_OPEN
    # ONE probe failure re-opens (no need to burn the threshold again) and
    # the cooldown restarts from now
    assert br.on_failure(6.0) is True
    assert br.state == OPEN and br.opened_at == 6.0 and br.trips == 2


# ---------------------------------------------------------------------------
# (2) EngineHealth registry: masks, probes, degrade, stragglers
# ---------------------------------------------------------------------------

def test_mask_grants_single_half_open_probe():
    t, clock = _fake_clock()
    h = EngineHealth(failure_threshold=1, cooldown_s=5.0, time_fn=clock)
    h.record_failure("kv_sparse")
    mask, probes = h.mask_for_request()
    assert "kv_sparse" in mask and probes == ()
    t[0] = 5.0                                  # cooldown elapses
    mask1, probes1 = h.mask_for_request()       # first request: the probe
    assert "kv_sparse" not in mask1 and probes1 == ("kv_sparse",)
    mask2, probes2 = h.mask_for_request()       # concurrent second request
    assert "kv_sparse" in mask2 and probes2 == ()
    h.release_probes(probes1)                   # plan never touched it
    _, probes3 = h.mask_for_request()
    assert probes3 == ("kv_sparse",)            # grantable again


def test_degrade_mask_spares_always_up_engines():
    h = EngineHealth()
    mask = h.degrade_mask()
    assert not mask & set(DEFAULT_ALWAYS_UP)
    assert mask == {"kv_sparse", "stream"}


def test_straggler_flag_counts_as_breaker_failure():
    t, clock = _fake_clock()
    h = EngineHealth(failure_threshold=1, straggler_z=3.0,
                     straggler_warmup=4, time_fn=clock)
    rng = np.random.default_rng(0)              # seeded: an unlucky global
    for _ in range(8):                          # stream can z-flag a warm-up
        h.after_plan([("stream", 0.010 + 0.001 * rng.random())])
    assert h.state("stream") == CLOSED
    h.after_plan([("stream", 10.0)])            # pathological straggler
    assert h.state("stream") == OPEN and h.trips() == 1


def test_straggler_floor_suppresses_jitter_flags():
    t, clock = _fake_clock()
    h = EngineHealth(failure_threshold=1, straggler_z=3.0,
                     straggler_warmup=4, straggler_min_s=0.05, time_fn=clock)
    for i in range(8):                          # small nonzero variance
        h.after_plan([("stream", 0.001 + 0.0001 * i)])
    h.after_plan([("stream", 0.010)])           # z-outlier, but sub-floor
    assert h.state("stream") == CLOSED
    h.after_plan([("stream", 10.0)])            # real pathological slowness
    assert h.state("stream") == OPEN


def test_snapshot_reports_states():
    h = EngineHealth(failure_threshold=1)
    h.record_failure("stream")
    snap = h.snapshot()
    assert snap["stream"]["state"] == OPEN and snap["stream"]["trips"] == 1
    assert snap["dense_array"]["state"] == CLOSED


# ---------------------------------------------------------------------------
# (3) masked planning
# ---------------------------------------------------------------------------

def test_node_candidates_mask_and_plan_infeasible():
    node = relational.select("T", column="v", lo=0.0)
    assert "columnar" in node_candidates(node)
    with pytest.raises(PlanInfeasible) as ei:
        node_candidates(node, mask=frozenset({"columnar"}))
    assert ei.value.op == "select" and "columnar" in ei.value.masked


def test_masked_dp_matches_exhaustive_and_avoids_engine():
    bd = BigDAWG(train_plans=2)
    rng = np.random.default_rng(0)
    bd.register("waves", DenseTensor(jnp.asarray(
        rng.normal(size=(16, 64)).astype(np.float32))), engine="dense_array")
    q = _portable_query()
    mask = frozenset({"dense_array"})
    ranked = dp_plans(q, bd.catalog, max_plans=4, cost_model=bd.cost_model,
                      mask=mask)
    exact = exhaustive_plans(q, bd.catalog, cost_model=bd.cost_model,
                             mask=mask)
    assert ranked[0][1].key == exact[0][1].key
    assert ranked[0][0] == pytest.approx(exact[0][0])
    for _, plan in ranked:
        assert all(eng != "dense_array" for _, eng in plan.assignment)


def test_masked_cache_entries_not_persisted(tmp_path):
    bd = BigDAWG()
    sig = "array.tfidf(array.haar(dense[8x6]))"
    mkey = masked_sig(sig, frozenset({"dense_array"}))
    assert mkey == sig + MASK_SEP + "dense_array"
    from repro.core.middleware import CachedPlan
    from repro.core.planner import Plan
    plan = Plan(((0, "columnar"), (1, "columnar")))
    bd.plan_cache[sig] = CachedPlan(plan)
    bd.plan_cache[mkey] = CachedPlan(plan)
    path = str(tmp_path / "plans.json")
    bd.save_plan_cache(path)
    with open(path) as f:
        entries = json.load(f)["entries"]
    assert sig in entries and mkey not in entries


# ---------------------------------------------------------------------------
# (4) error taxonomy
# ---------------------------------------------------------------------------

def test_error_taxonomy_subclasses():
    for cls in (EngineDown, PlanInfeasible, Overloaded, QueryParseError):
        assert issubclass(cls, BigDAWGError)
    assert issubclass(QueryParseError, ValueError)   # pre-taxonomy contract
    e = EngineDown("kv_sparse", "tfidf", TimeoutError("t"))
    assert e.engine == "kv_sparse" and e.op == "tfidf"
    assert isinstance(e.cause, TimeoutError)
    p = PlanInfeasible("select", "relational", masked=("columnar",))
    assert p.island == "relational" and p.masked == ("columnar",)


def test_is_engine_failure_classification():
    assert is_engine_failure(TimeoutError())
    assert is_engine_failure(ConnectionError())
    assert is_engine_failure(SimulatedFailure("injected"))
    assert not is_engine_failure(KeyError("column"))
    assert not is_engine_failure(ValueError("bad query"))


def test_shed_alias_contract():
    # the pre-taxonomy name must keep working: construction, isinstance,
    # and the query/reason attributes the PR 5 tests rely on
    assert Shed is Overloaded
    r = Shed("q")
    assert isinstance(r, Overloaded) and isinstance(r, BigDAWGError)
    assert r.query == "q" and r.reason == "max_pending"
    assert r.status == "shed"


# ---------------------------------------------------------------------------
# (5) failover end to end: outage -> degraded serve -> recovery
# ---------------------------------------------------------------------------

def test_failover_and_recovery_restore_incumbent():
    # one trained plan: the DP's best, dense_array only.  With two, the
    # incumbent is whichever ran faster on the wall clock, and a
    # two-engine incumbent (dense_array + kv_sparse) can fail over to a plan
    # that never touches, so never trips, its second engine
    s, health, inj, t = _resilient_session(threshold=2, cooldown=5.0,
                                           train_plans=1)
    q = _portable_query()
    s.execute(q, mode="training")
    r_ok = s.execute(q)
    assert r_ok.mode == "production" and r_ok.status == "ok"
    assert not r_ok.degraded and r_ok.failovers == 0
    incumbent = r_ok.plan_key
    down = {eng for _, eng in _plan_from_key(incumbent).assignment}
    for eng in down:
        inj.fail_engine(eng)

    # outage: EngineDown retries burn the threshold, the breaker opens, and
    # the request is re-planned around the dead engine(s) — it still succeeds
    r_deg = s.execute(q)
    assert r_deg.status == "degraded" and r_deg.degraded
    assert r_deg.failovers >= 1
    deg_engines = {eng for _, eng in _plan_from_key(r_deg.plan_key).assignment}
    assert not deg_engines & down
    assert all(health.state(eng) == OPEN for eng in down)
    assert health.trips() == len(down)

    # second degraded request serves the mask-keyed cache entry: no DP, no
    # further failovers
    r_deg2 = s.execute(q)
    assert r_deg2.status == "degraded" and r_deg2.failovers == 0
    assert r_deg2.report.cache_hit and r_deg2.plan_key == r_deg.plan_key

    # recovery: cooldown elapses, the half-open probe request plans unmasked
    # and — because masked serves were recorded under the mask-suffixed
    # signature — the monitor still names the incumbent, restored verbatim
    for eng in down:
        inj.recover(eng)
    t[0] += 5.0
    r_rec = s.execute(q)
    assert r_rec.status == "ok" and not r_rec.degraded
    assert r_rec.plan_key == incumbent
    assert all(health.state(eng) == CLOSED for eng in down)
    assert health.trips() == len(down)          # no new trips on recovery
    assert s.bigdawg.failovers == r_deg.failovers


def test_query_error_propagates_raw_and_never_feeds_breaker():
    s, health, inj, t = _resilient_session()
    bad = relational.select("T")                # missing the column attr
    with pytest.raises(KeyError):               # NOT EngineDown
        s.execute(bad)
    assert health.state("columnar") == CLOSED and health.trips() == 0


def test_plan_infeasible_when_only_capable_engine_is_down():
    s, health, inj, t = _resilient_session(threshold=1)
    q = relational.select("T", column="v", lo=0.0)   # columnar-only op
    inj.fail_engine("columnar")
    with pytest.raises(PlanInfeasible):
        s.execute(q)
    assert health.state("columnar") == OPEN


def test_server_zero_failed_requests_under_injected_outage():
    s, health, inj, t = _resilient_session(threshold=2)
    q = _portable_query()
    srv = QueryServer(s.bigdawg)
    srv.warm([q])
    inj.fail_engine("dense_array")
    reports = srv.submit_many([_portable_query() for _ in range(6)],
                              workers=2)
    # zero failed requests: every slot is a served Report, none raised and
    # none were shed
    assert len(reports) == 6
    assert all(not isinstance(r, Overloaded) for r in reports)
    assert all(r.result is not None for r in reports)
    assert srv.stats["failovers"] >= 1
    assert srv.stats["breaker_trips"] >= 1
    assert srv.stats["degraded"] >= 1
    assert any(r.status == "degraded" for r in reports)


# ---------------------------------------------------------------------------
# (6) async Session API
# ---------------------------------------------------------------------------

def test_execute_async_returns_future_of_result():
    s, health, inj, t = _resilient_session()
    fut = s.execute_async(_portable_query())
    r = fut.result(timeout=60)
    assert r.mode == "training" and r.status == "ok"
    assert r.failovers == 0 and not r.degraded


def test_map_preserves_input_order():
    s, health, inj, t = _resilient_session()
    qs = [_portable_query(),
          relational.select("T", column="v", lo=0.0)]
    out = s.map(qs, workers=2)
    assert [r.sig for r in out] == \
        [signature(q, s.bigdawg.catalog) for q in qs]


def test_execute_async_parse_error_is_eager():
    s, health, inj, t = _resilient_session()
    with pytest.raises(QueryParseError):        # at the call site, not in
        s.execute_async("RELATIONAL(select from)")   # the future
    with pytest.raises(QueryParseError):
        s.map(["RELATIONAL(select * from T)", "RELATIONAL(oops"])


# ---------------------------------------------------------------------------
# (7) qlang SQL surface
# ---------------------------------------------------------------------------

def test_sql_select_matches_programmatic_signature():
    q_sql = parse_text("RELATIONAL(select * from A where v >= 0.5 and v <= 2)")
    q_api = relational.select("A", column="v", lo=0.5, hi=2)
    assert signature(q_sql, None) == signature(q_api, None)


def test_sql_where_folds_bounds_per_column():
    q = parse_text("RELATIONAL(select * from A "
                   "where v >= 0.5 and v < 2.5 and v >= 1.0)")
    assert q.op == "select"
    assert q.attrs["lo"] == 1.0 and q.attrs["hi"] == 2.5   # tightest bounds
    qe = parse_text("RELATIONAL(select * from A where v = 3)")
    assert qe.attrs["lo"] == 3 and qe.attrs["hi"] == 3     # equality pins


def test_sql_column_list_projects():
    q = parse_text("RELATIONAL(select a, b from A where v > 0)")
    assert q.op == "project" and q.attrs["columns"] == ["a", "b"]
    assert q.inputs[0].op == "select" and q.inputs[0].attrs["column"] == "v"
    bare = parse_text("RELATIONAL(select * from A)")
    assert bare.op == "scope"                   # plain table reference


def test_sql_errors_and_island_guard():
    for text in ("RELATIONAL(select from A)",       # no columns
                 "RELATIONAL(select * A)",          # missing FROM
                 "RELATIONAL(select * from)",       # missing table
                 "RELATIONAL(select * from A where v > x)",  # non-numeric
                 "ARRAY(select * from A)"):         # relational-only syntax
        with pytest.raises(QueryParseError):
            parse_text(text)


def test_sql_pipeline_placeholder():
    q = parse_text("RELATIONAL(join(A, B, left_on=k, right_on=k)) "
                   "|> RELATIONAL(select * from _ where v > 0)")
    assert q.op == "select" and q.inputs[0].op == "join"


# ---------------------------------------------------------------------------
# (8) adaptive shedding (AIMD bound, degrade-before-shed)
# ---------------------------------------------------------------------------

class _FakeReport:
    def __init__(self, mode="production"):
        self.mode = mode
        self.cache_hit = mode == "production"
        self.replanned = False
        self.explored = False
        self.degraded = False
        self.failovers = 0
        self.status = "ok"


class _FakeBD:
    """Stand-in middleware: instant (or slow) serves, records degrade flags."""

    def __init__(self, mode="production", delay=0.0, health=None):
        self.mode = mode
        self.delay = delay
        self.health = health
        self.degrade_calls = []

    def execute(self, query, mode="auto", degrade=False):
        self.degrade_calls.append(degrade)
        if self.delay:
            time.sleep(self.delay)
        return _FakeReport(self.mode)


def test_adaptive_bound_grows_under_target():
    srv = QueryServer(_FakeBD(), latency_target_s=10.0)
    b0 = srv._bound
    for _ in range(5):
        srv.submit("q")
    assert srv._bound == b0 + 5
    assert srv.stats["latency_ewma"] > 0.0
    assert srv.stats["shed"] == 0


def test_adaptive_bound_halves_over_target_with_floor():
    srv = QueryServer(_FakeBD(delay=0.002), latency_target_s=1e-6)
    for _ in range(12):
        srv.submit("q")
    assert srv._bound == 1.0                    # halved down to the floor


def test_adaptive_bound_capped_at_max_pending():
    srv = QueryServer(_FakeBD(), max_pending=9, latency_target_s=10.0)
    assert srv._bound == 9.0
    for _ in range(5):
        srv.submit("q")
    assert srv._bound == 9.0


def test_training_requests_excluded_from_latency_ewma():
    srv = QueryServer(_FakeBD(mode="training"), latency_target_s=10.0)
    b0 = srv._bound
    srv.submit("q")
    assert srv.stats["latency_ewma"] == 0.0 and srv._bound == b0


def test_degrade_before_shed_admission_ladder():
    bd = _FakeBD(health=object())               # middleware CAN degrade
    srv = QueryServer(bd, latency_target_s=10.0)
    bound = int(srv._bound)
    srv._pending = bound                        # at the bound: degrade rung
    assert srv._try_admit() == "degrade"
    srv._pending = 2 * bound                    # past twice the bound: shed
    assert srv._try_admit() is None
    assert srv.stats["shed"] == 1
    # without a health registry there is no degraded planning: shed directly
    srv2 = QueryServer(_FakeBD(health=None), latency_target_s=10.0)
    srv2._pending = int(srv2._bound)
    assert srv2._try_admit() is None


def test_degraded_admission_reaches_middleware():
    bd = _FakeBD(health=object())
    srv = QueryServer(bd, latency_target_s=10.0)
    pend0 = srv._pending = int(srv._bound)      # force the degrade rung
    out = srv.submit_many(["q"], workers=1)
    assert len(out) == 1 and not isinstance(out[0], Overloaded)
    assert bd.degrade_calls == [True]
    assert srv.stats["degraded"] == 0           # fake report isn't degraded
    assert srv._pending == pend0                # slot released


def test_overloaded_reason_names_the_policy():
    srv = QueryServer(_FakeBD(), latency_target_s=10.0)
    srv._pending = 2 * int(srv._bound)
    out = srv.submit_many(["q"], workers=1)
    assert isinstance(out[0], Shed) and out[0].reason == "latency_target"
    legacy = QueryServer(_FakeBD(), max_pending=1)
    legacy._pending = 1
    out = legacy.submit_many(["q"], workers=1)
    assert isinstance(out[0], Overloaded) and out[0].reason == "max_pending"


# ---------------------------------------------------------------------------
# (9) BatchServer on the shared request pool
# ---------------------------------------------------------------------------

def _toy_batch_server(slots=3, max_len=16, V=8):
    def init_cache(b, ml):
        return {"k": jnp.zeros((b, ml, 2), jnp.float32)}

    def prefill(params, tok):
        first = int(np.asarray(tok).sum()) % V
        logits = jnp.zeros((1, V), jnp.float32).at[0, first].set(1.0)
        rows = {"k": jnp.ones((1, tok.shape[1], 2), jnp.float32)}
        return logits, rows, tok.shape[1]

    def decode(params, cache, tokens, pos):
        return (tokens + 1) % V, cache

    return BatchServer(slots=slots, max_len=max_len, prefill_fn=prefill,
                       decode_fn=decode, params=None,
                       init_cache_fn=init_cache)


def test_batchserver_serve_matches_run():
    rng = np.random.default_rng(3)
    def reqs():
        return [Request(rid=i,
                        prompt=rng.integers(1, 5, 3 + i % 4).astype(np.int32),
                        max_new_tokens=5) for i in range(7)]
    rng = np.random.default_rng(3)
    seq = _toy_batch_server().run(reqs())
    rng = np.random.default_rng(3)
    par = _toy_batch_server().serve(reqs(), workers=3)
    assert all(r.done for r in par)
    assert [r.out_tokens for r in par] == [r.out_tokens for r in seq]
