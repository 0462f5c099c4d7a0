"""The program's spans on the profile's clock: the anchors of a profile
recorded here on the CPU map every request's spans over its XLA ops; the
idle readers on hand-made spans and gaps; and the new span readers find
nothing in a program that records no such spans."""
from types import SimpleNamespace

import numpy as np
import pytest

import loadgen
import timeline
import trace_reduce
from harness import BENCH, reader

MS = 1e6                  # nanoseconds in a millisecond


def run_of(span_lists, profile=None, tl="unset"):
    outs = [loadgen.Outcome(variant=0, due=0.0, queued=0.0, sent=0.0,
                            done=0.01, ok=True,
                            report={"seconds": 0.01, "cast_bytes": 0.0,
                                    "spans": s})
            for s in span_lists]
    extra = {"profiled": None}
    if tl != "unset":
        extra["timeline"] = tl
    return SimpleNamespace(
        window=loadgen.Window(outs, seconds=10.0, deadline=70.0,
                              loop="closed"),
        profile=profile, extra=extra)


def read(name, run):
    return reader(BENCH, "layers", name)(run)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Three traced serves of a two-op plan inside a CPU profile."""
    import jax
    import jax.numpy as jnp
    from repro.core import BigDAWG, DenseTensor, array
    bd = BigDAWG(train_plans=1, trace=True)
    x = np.random.default_rng(0).normal(size=(256, 256)).astype(np.float32)
    bd.register("X", DenseTensor(jnp.asarray(x)), "dense_array")
    q = array.tfidf(array.matmul("X", "X"))
    bd.execute(q, mode="training")
    for _ in range(2):
        bd.execute(q)
    d = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(d))
    reps = [bd.execute(q) for _ in range(3)]
    jax.profiler.stop_trace()
    profile = trace_reduce.reduce(trace_reduce.find_xplane(str(d)), 600e9,
                                  cpu_ops=True)
    run = run_of([r.trace.to_dict()["spans"] for r in reps], profile=profile)
    return run, timeline.load(run, str(d)), str(d)


def test_anchors_pair_with_their_annotations(recorded):
    run, tl, d = recorded
    anchors = [s for o in run.window.outcomes for s in o.report["spans"]
               if s["name"] == "clock_anchor"]
    events = timeline.anchor_events(trace_reduce.find_xplane(d))
    assert len(anchors) == 3 and set(events) == {a["parent"] for a in anchors}
    off = timeline.offset_ns(anchors, events)
    for a in anchors:
        lo, hi = events[a["parent"]]
        # the annotation lies between the two perf_counter readings
        assert 1e9 * a["start"] + off <= lo + 1e4
        assert hi <= 1e9 * (a["start"] + a["seconds"]) + off + 1e4


def test_mapped_requests_cover_their_xla_ops(recorded):
    """Every XLA op of the profile ran inside a request as mapped: the one
    offset puts the spans on the profile's clock."""
    run, tl, _ = recorded
    reqs = [(s.lo, s.hi) for s in tl.spans if s.name == "request"]
    ops = tl.planes[0]
    assert len(reqs) == 3 and ops
    slack = 0.05 * MS
    outside = [op for op in ops if not any(
        lo - slack <= op.start and op.end <= hi + slack for lo, hi in reqs)]
    assert outside == []
    share = timeline.idle_share_with(tl, ("request",))
    assert 0.0 < share <= tl.idle_pct == read("idle_share", run)


def synthetic():
    """Window 10 ms; device busy [0, 2) and [5, 6); request A over [1, 8)
    with an engine op over [2.5, 4); request B over [9, 9.5); a host frame
    over the whole window."""
    span = timeline.Span
    return timeline.Timeline(
        10 * MS, 70.0, [[trace_reduce.Op("fusion", 0, 2 * MS),
                   trace_reduce.Op("haar_pallas", 5 * MS, 1 * MS)]],
        [span("request", "request", 1 * MS, 8 * MS),
         span("engine_op", "engine_op:haar", 2.5 * MS, 4 * MS),
         span("request", "request", 9 * MS, 9.5 * MS)],
        [trace_reduce.Op("$server.py:300 submit", 0, 10 * MS)])


@pytest.mark.parametrize("name,want", [
    # idle [2, 5) and [6, 10); requests open over 3 + 2 + 0.5 ms of it
    ("idle_in_request", 55.0), ("idle_in_request.closed", 55.0),
    # the engine op over 1.5 ms of it
    ("idle_dispatch", 15.0), ("idle_dispatch.closed", 15.0)])
def test_idle_readers_on_synthetic_spans(name, want):
    run = run_of([], profile=SimpleNamespace(window_ns=10 * MS),
                 tl=synthetic())
    assert read(name, run) == pytest.approx(want)


def test_gaps_take_the_innermost_span_over_them():
    # [6, 10) has no span over all of it: the host frame labels it
    assert timeline.gap_labels(synthetic()) == [
        ("$server.py:300 submit", pytest.approx(4e-3)),
        ("request", pytest.approx(3e-3))]


def test_span_readers_mean_per_request():
    def req(lock, syncs):
        return ([{"name": "request", "sid": "r", "parent": None,
                  "start": 0.0, "seconds": 0.1},
                 {"name": "sig_lock_wait", "sid": "w", "parent": "r",
                  "start": 0.0, "seconds": lock}]
                + [{"name": "host_sync", "sid": f"h{i}", "parent": "r",
                    "start": 0.01, "seconds": s}
                   for i, s in enumerate(syncs)])
    run = run_of([req(0.004, [0.001, 0.002]), req(0.0, [0.005])])
    assert read("lock_wait_ms", run) == pytest.approx(2.0)
    assert read("host_sync_ms.closed", run) == pytest.approx(4.0)


@pytest.mark.parametrize("name", ["lock_wait_ms", "host_sync_ms",
                                  "idle_in_request", "idle_dispatch"])
def test_new_readers_find_nothing_in_an_older_program(name):
    """Spans without the new names or anchors, as a program that records
    none gives them: the readers return None and do not raise."""
    old = [{"name": "request", "sid": "r", "parent": None, "seconds": 0.1},
           {"name": "engine_op", "sid": "e", "parent": "r", "seconds": 0.05}]
    run = run_of([old], profile=SimpleNamespace(window_ns=10 * MS))
    assert read(name, run) is None


def test_ids_that_read_as_numbers_still_pair(tmp_path):
    """A span id such as ``703e-1234`` (a pid in hex, then a count) is a
    float literal; the anchor's stat still comes back as that id."""
    import jax
    from repro.core import device
    from repro.core.tracing import Tracer
    span = Tracer(True).start().root("request")
    span.sid = "703e-1234"
    jax.profiler.start_trace(str(tmp_path))
    device.clock_anchor(span)
    jax.profiler.stop_trace()
    events = timeline.anchor_events(trace_reduce.find_xplane(str(tmp_path)))
    assert list(events) == ["703e-1234"]
