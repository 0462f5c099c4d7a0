"""Adaptive planning loop (ISSUE 2): measured-size feedback, online
re-planning on predicted/measured divergence, multi-hop cast routing, and
warm plan-cache persistence.

Covers the four tentpole behaviors end to end: a data-dependent select gets
its real size from the monitor (beating the shape rule), >2x divergence
triggers exactly one re-plan, a persisted plan cache round-trips into a
fresh ``BigDAWG`` that serves production with zero plan enumerations, and
the migrator routes coo->dense->columnar when the direct pair is calibrated
slow.
"""
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (BigDAWG, CachedPlan, CostModel, DenseTensor, Monitor,
                        array, relational, estimate_sizes, execute_plan)
from repro.core import cast as castmod
from repro.core.costmodel import observed_nbytes
from repro.core.ioutil import atomic_json_dump, load_json
from repro.core.middleware import _plan_from_key, default_plan_cache_path
from repro.core.migrator import Migrator
from repro.core.planner import Plan


def _bd(tmp_path=None, n=32, t=64, lo_frac=0.5, **kw):
    monitor = Monitor(str(tmp_path / "monitor.json")) if tmp_path else None
    bd = BigDAWG(monitor=monitor, train_plans=4, **kw)
    rng = np.random.default_rng(0)
    bd.register("waves", DenseTensor(jnp.asarray(
        rng.normal(size=(n, t)).astype(np.float32))), engine="dense_array")
    return bd


def _selective():
    # select keeps ~30% of a standard normal: genuinely data-dependent size
    return array.tfidf(array.haar(
        relational.select("waves", column="value", lo=0.5), levels=2))


# ---------------------------------------------------------------------------
# (1) measured-size feedback
# ---------------------------------------------------------------------------

def test_measured_size_overrides_shape_rule():
    bd = _bd()
    q = _selective()
    static = estimate_sizes(q, bd.catalog)
    sel = q.nodes()[0]                       # post-order: select is first
    # the shape rule can only say "output ~ input"
    assert static[sel.uid] == 4.0 * 32 * 64

    rep = bd.execute(q, mode="training")
    measured = bd.monitor.measured_sizes(rep.sig)
    assert 0 in measured
    # ~30% of a standard normal is >= 0.5: the measured size must be far
    # below the shape rule's input-sized guess
    assert measured[0] < 0.6 * static[sel.uid]

    fb = estimate_sizes(q, bd.catalog, measured=measured)
    assert fb[sel.uid] == pytest.approx(measured[0])
    assert fb[sel.uid] < static[sel.uid]


def test_executor_reports_size_obs_in_both_modes():
    bd = _bd()
    q = _selective()
    plan = Plan(tuple((i, "dense_array") for i in range(len(q.nodes()))))
    seq = execute_plan(q, plan, bd.catalog)
    conc = execute_plan(q, plan, bd.catalog, concurrent=True)
    assert set(seq.size_obs) == set(conc.size_obs) == {0, 1, 2}
    for pos in seq.size_obs:
        assert seq.size_obs[pos] == pytest.approx(conc.size_obs[pos])


def test_observed_nbytes_is_valid_aware():
    d = DenseTensor(jnp.ones((4, 4)), valid_count=3)
    assert observed_nbytes(d) == 12.0                      # 3 live cells
    col = castmod.cast(DenseTensor(jnp.ones((4, 4))), "columnar")
    assert observed_nbytes(col) == 4.0 * 16
    from repro.core.engines import ENGINES
    masked = ENGINES["columnar"].run("select", {"column": "value", "lo": 2.0},
                                     col)
    assert observed_nbytes(masked) == 0.0                  # nothing matches


def test_monitor_sizes_persist_and_legacy_format_loads(tmp_path):
    p = tmp_path / "monitor.json"
    m = Monitor(str(p))
    m.record("sig", "0:dense_array", 0.1, sizes={0: 100.0, 1: 200.0})
    m.record("sig", "0:dense_array", 0.1, sizes={0: 300.0})
    m.save()
    m2 = Monitor(str(p))
    assert m2.measured_sizes("sig") == {0: 200.0, 1: 200.0}   # running mean
    # a format-1 file (bare {sig: {plan_key: stats}}) still loads
    legacy = tmp_path / "legacy.json"
    atomic_json_dump(str(legacy), {"sig": {"0:dense_array": {
        "mean_seconds": 0.5, "n": 2, "last_seconds": 0.4,
        "cast_bytes": 0.0, "usage": {}, "extra": {}}}})
    m3 = Monitor(str(legacy))
    assert m3.best("sig")[0] == "0:dense_array"
    assert m3.measured_sizes("sig") == {}


# ---------------------------------------------------------------------------
# (2) online re-planning on divergence
# ---------------------------------------------------------------------------

def test_divergence_triggers_exactly_one_replan():
    """One divergence event -> one cheap-DP re-plan, and the replacement
    baseline is measurement-anchored so the same measured cost does not
    re-trigger (controlled measured values: wall-clock noise on ~ms queries
    can exceed the factor by itself and must not drive this assertion)."""
    bd = _bd()
    q = _selective()
    rep = bd.execute(q, mode="training")
    entry = bd.plan_cache[rep.sig]
    entry.pinned = entry.restored = False
    base = bd.replans
    mean = bd.monitor.known_plans(rep.sig)[entry.plan.key].mean_seconds
    entry.predicted_s = mean * 10.0          # make the baseline lie by 10x
    assert bd._maybe_replan(q, rep.sig, mean, entry)
    assert bd.replans == base + 1
    # the replacement entry's baseline is self-consistent: a measured cost
    # matching it must not re-plan (no cascade)
    new_entry = bd.plan_cache[rep.sig]
    new_entry.pinned = False
    assert not bd._maybe_replan(q, rep.sig, new_entry.predicted_s, new_entry)
    assert bd.replans == base + 1


def test_no_replan_within_factor():
    bd = _bd()
    q = _selective()
    rep = bd.execute(q, mode="training")
    entry = bd.plan_cache[rep.sig]
    entry.pinned = entry.restored = False
    # 1.5x off is inside the 2x factor: no re-plan in either direction
    assert not bd._maybe_replan(q, rep.sig, entry.predicted_s * 1.5, entry)
    assert not bd._maybe_replan(q, rep.sig, entry.predicted_s / 1.5, entry)
    assert bd.replans == 0


def test_replanned_entry_is_served_and_recorded():
    bd = _bd()
    q = _selective()
    rep = bd.execute(q, mode="training")
    # force a divergence AND a cost model under which a different plan wins,
    # so the re-plan produces a genuinely new cache entry
    entry = bd.plan_cache[rep.sig]
    entry.pinned = entry.restored = False
    entry.predicted_s *= 50.0
    for op in ("select", "haar", "tfidf"):
        bd.cost_model.observe_op("columnar", op, 1e6, 1e-4)
        bd.cost_model.observe_op("dense_array", op, 1e6, 10.0)
    rep2 = bd.execute(q, mode="production")
    assert rep2.replanned
    new_key = bd.plan_cache[rep.sig].plan.key
    assert bd.plan_cache[rep.sig].pinned
    rep3 = bd.execute(q, mode="production")
    assert rep3.plan_key == new_key          # pinned serve of the new plan
    assert rep3.cache_hit
    assert new_key in bd.monitor.known_plans(rep.sig)


# ---------------------------------------------------------------------------
# (3) multi-hop cast routing
# ---------------------------------------------------------------------------

def _routing_model():
    cm = CostModel()
    cm.observe_cast("coo", "columnar", 1e3, 1.0)       # 1e3 B/s: awful direct
    cm.observe_cast("coo", "dense", 1e6, 0.001)        # 1e9 B/s
    cm.observe_cast("dense", "columnar", 1e6, 0.001)   # 1e9 B/s
    return cm


def test_multi_hop_route_beats_slow_direct_pair():
    cm = _routing_model()
    seconds, path = cm.cast_route("coo", "columnar", 1e6)
    assert path == ["coo", "dense", "columnar"]
    direct = cm._edge_seconds("coo", "columnar", 1e6)
    assert seconds < direct / 100.0
    assert cm.cast_seconds("coo", "columnar", 1e6) == pytest.approx(seconds)


def test_unobserved_multi_hop_never_beats_measured_direct():
    cm = CostModel()
    cm.observe_cast("dense", "coo", 1e6, 0.25)         # slow but MEASURED
    # default-bandwidth detours exist on paper; they must not win
    assert cm.cast_seconds("dense", "coo", 1e6) == pytest.approx(0.25,
                                                                 rel=0.1)


def test_migrator_executes_routed_multi_hop():
    cm = _routing_model()
    rng = np.random.default_rng(0)
    dense = DenseTensor(jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32)))
    coo = castmod.cast(dense, "coo")
    mig = Migrator(cost_model=cm)
    out = mig.to_engine(coo, "columnar")
    assert out.kind == "columnar"
    hops = [(s, d) for s, d, _, _ in mig.events]
    assert hops == [("coo", "dense"), ("dense", "columnar")]
    np.testing.assert_allclose(
        np.asarray(castmod.cast(out, "dense").data),
        np.asarray(dense.data), rtol=1e-6)
    # without a model the migrator still takes the registered direct pair
    mig2 = Migrator()
    mig2.to_engine(coo, "columnar")
    assert [(s, d) for s, d, _, _ in mig2.events] == [("coo", "columnar")]


def test_unregistered_pair_still_routes_through_dense():
    cm = CostModel()
    s, path = cm.cast_route("columnar", "stream", 1e4)
    assert path[0] == "columnar" and path[-1] == "stream"
    assert all(p in castmod._CASTS for p in zip(path, path[1:]))


# ---------------------------------------------------------------------------
# (4) plan-cache persistence + warm restart
# ---------------------------------------------------------------------------

def test_plan_cache_roundtrips_and_fresh_bigdawg_serves_warm(tmp_path,
                                                             monkeypatch):
    bd = _bd(tmp_path)
    q = _selective()
    rep = bd.execute(q, mode="training")
    assert (tmp_path / "monitor.plans.json").exists()
    assert default_plan_cache_path(str(tmp_path / "monitor.json")) == \
        str(tmp_path / "monitor.plans.json")
    bd.execute(q, mode="production")         # at least one production serve
    # align the entry with the monitor's current best (online re-planning may
    # legitimately have pinned a different plan mid-flight) and persist —
    # the explicit flush QueryServer.persist() performs
    key, stats, _ = bd.monitor.best(rep.sig)
    bd.plan_cache[rep.sig] = CachedPlan(_plan_from_key(key),
                                        stats.mean_seconds)
    bd.monitor.save()
    bd.save_plan_cache()

    # fresh middleware on the same dir: must serve production from the
    # persisted cache with ZERO plan enumerations
    bd2 = _bd(tmp_path)
    assert rep.sig in bd2.plan_cache
    assert bd2.plan_cache[rep.sig].plan.key == key
    assert bd2.plan_cache[rep.sig].restored

    import repro.core.middleware as mw

    def boom(*a, **kw):
        raise AssertionError("fresh process enumerated plans")

    monkeypatch.setattr(mw, "dp_plans", boom)
    rep2 = bd2.execute(q, mode="production")
    assert rep2.mode == "production"
    assert rep2.cache_hit and not rep2.replanned
    assert rep2.plan_key == key


def test_malformed_persisted_entries_are_skipped_with_warning(tmp_path):
    path = tmp_path / "monitor.plans.json"
    atomic_json_dump(str(path), {"format": 1, "entries": {
        "goodsig": {"plan": "0:dense_array|1:dense_array", "predicted_s": 0.1},
        "badsig1": {"plan": "0:dense_array|garbage"},
        "badsig2": {"plan": "0:no_such_engine"},
        "badsig3": "not-an-object",
        "badsig4": {"predicted_s": 0.5},                  # missing plan key
    }})
    bd = BigDAWG(monitor=Monitor(str(tmp_path / "monitor.json")))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        bd.load_plan_cache(str(path))
    assert list(bd.plan_cache) == ["goodsig"]
    assert bd.plan_cache["goodsig"].restored
    assert len(w) == 4


def test_plan_from_key_rejects_malformed():
    assert _plan_from_key("0:dense_array|1:columnar").key == \
        "0:dense_array|1:columnar"
    for bad in ("", "garbage", "0:dense_array|x:y:z", "a:dense_array",
                "0:not_an_engine", "1:dense_array",         # gap at 0
                "0:dense_array|0:columnar"):                # duplicate pos
        with pytest.raises(ValueError):
            _plan_from_key(bad)


def test_unparseable_plan_cache_file_starts_cold(tmp_path):
    mon = tmp_path / "monitor.json"
    bad = tmp_path / "monitor.plans.json"
    bad.write_text('{"entries": {"sig1": {"plan": "0:dense')   # truncated
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        bd = BigDAWG(monitor=Monitor(str(mon)))
    assert bd.plan_cache == {}
    assert any("unreadable" in str(x.message) for x in w)


def test_wrong_length_plan_falls_back_to_training(tmp_path):
    bd = _bd()
    q = _selective()
    rep = bd.execute(q, mode="training")
    # history and cache claim a 1-position plan for this 3-node query
    stats = bd.monitor.db[rep.sig].pop(rep.plan_key)
    bd.monitor.db[rep.sig] = {"0:dense_array": stats}
    bd.plan_cache[rep.sig] = CachedPlan(_plan_from_key("0:dense_array"),
                                        stats.mean_seconds)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        rep2 = bd.execute(q, mode="production")
    assert rep2.mode == "training"           # retrained, did not crash
    assert any("positions" in str(x.message) for x in w)


def test_background_queue_skips_corrupted_plan_keys():
    bd = _bd()
    q = _selective()
    rep = bd.execute(q, mode="training")
    good = bd.monitor.best(rep.sig)[0]
    bd.monitor.queue_background(rep.sig, "not:a|plan")       # corrupted
    bd.monitor.queue_background(rep.sig, "0:dense_array")    # wrong length
    bd.monitor.queue_background(rep.sig, good)               # fine
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        done = bd.run_background_queue({rep.sig: q})
    assert done == 1                         # drained past both bad entries
    assert len(w) == 2


def test_corrupted_monitor_best_falls_back_to_training(tmp_path):
    bd = _bd()
    q = _selective()
    rep = bd.execute(q, mode="training")
    # corrupt the whole history for this sig: production must retrain, not die
    bd.monitor.db[rep.sig] = {"totally:broken:key":
                              bd.monitor.db[rep.sig][rep.plan_key]}
    bd.plan_cache.pop(rep.sig)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        rep2 = bd.execute(q, mode="production")
    assert rep2.mode == "training"


def test_restored_entry_without_baseline_adopts_measurement():
    """A persisted entry missing predicted_s (loads as 0.0) must still
    re-sync on first serve — a zero baseline must not leave the replan loop
    permanently dead for that signature."""
    bd = _bd()
    q = _selective()
    rep = bd.execute(q, mode="training")
    entry = bd.plan_cache[rep.sig]
    entry.predicted_s, entry.restored, entry.pinned = 0.0, True, False
    assert not bd._maybe_replan(q, rep.sig, 0.005, entry)
    assert not entry.restored
    assert entry.predicted_s == pytest.approx(0.005)     # baseline adopted


def test_restored_entry_resyncs_instead_of_replanning(tmp_path):
    # fusion off: when training picks a plan with a fused segment (a matter
    # of wall-clock timings), the restarted process's first serve is a cold
    # fused compile, a warm-up that by design defers the re-sync to the next
    # serve — the outcome would depend on which plan won training
    bd = _bd(tmp_path, fuse=False)
    q = _selective()
    rep = bd.execute(q, mode="training")
    # persist an entry aligned with the monitor's best plan whose baseline
    # will look 10x off to the next process (a "runtime changed" restart)
    key, stats, _ = bd.monitor.best(rep.sig)
    bd.plan_cache[rep.sig] = CachedPlan(_plan_from_key(key),
                                        stats.mean_seconds / 10.0)
    bd.save_plan_cache()
    bd2 = _bd(tmp_path, fuse=False)
    entry = bd2.plan_cache[rep.sig]
    assert entry.restored
    rep2 = bd2.execute(q, mode="production")
    assert not rep2.replanned and bd2.replans == 0
    assert not bd2.plan_cache[rep.sig].restored
    # prediction re-synced to this process's measured history
    want = bd2.monitor.known_plans(rep.sig)[rep2.plan_key].mean_seconds
    assert bd2.plan_cache[rep.sig].predicted_s == pytest.approx(want)
