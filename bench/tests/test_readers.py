"""The metric readers on a hand-made window: latencies over every request,
throughput inside the window, and the host-clock per-layer metrics leaving out the requests that overlap
the profiler's time."""
from types import SimpleNamespace

import pytest

import loadgen
from harness import BENCH, reader


def outcome(variant, due, sent, done, ok=True, seconds=0.01):
    return loadgen.Outcome(variant=variant, due=due, queued=sent, sent=sent,
                           done=done, ok=ok,
                           report={"seconds": seconds, "cast_bytes": 2e6,
                                   "spans": None})


def run_of(outcomes, loop, profiled=None):
    win = loadgen.Window(outcomes, seconds=10.0, deadline=70.0, loop=loop)
    return SimpleNamespace(window=win, setup_s=12.5, train_walls=[1.0, 2.0],
                           extra={"profiled": profiled}, profile=None,
                           memory_peak=3e9)


@pytest.fixture
def outs():
    # 19 requests of 100 ms sent 1 ms late; one failed; one done after
    # the window closed
    o = [outcome(0, i * 0.5, i * 0.5 + 0.001, i * 0.5 + 0.1)
         for i in range(19)]
    o.append(outcome(1, 9.9, 9.9, 10.4))
    o[3] = outcome(1, 1.5, 1.5, float("nan"), ok=False)
    return o


def read(kind, name, run):
    return reader(BENCH, kind, name)(run)


def test_end_to_end_readers(outs):
    run = run_of(outs, "open")
    assert read("end_to_end", "p50_ms", run) == pytest.approx(100.0)
    # 18 served inside the 10 s window
    assert read("end_to_end", "qps", run) == pytest.approx(1.8)
    assert read("end_to_end", "setup_s", run) == 12.5


def test_failed_request_is_the_slowest(outs):
    lat = run_of(outs, "open").window.latencies()
    assert max(lat) == pytest.approx(70.0 - 1.5)


def test_layer_readers_leave_out_the_profiled_time(outs):
    quiet = run_of(outs, "open", profiled=(2.0, 3.2))
    # requests due at 2.0, 2.5 and 3.0 overlap [2.0, 3.2]: 16 served left
    assert read("layers", "serve_ms", quiet) == pytest.approx(10.0)
    # 15 of them waited 89 ms outside Report.seconds, the late one 490 ms
    assert read("layers", "frontdoor_wait_ms", quiet) == pytest.approx(
        (15 * 89.0 + 490.0) / 16)
    assert read("layers", "gen_late_ms", quiet) == pytest.approx(1.0)
    assert read("layers", "cast_mb", quiet) == pytest.approx(2.0)
    assert read("layers", "train_s", quiet) == 3.0
    assert read("layers", "hbm_peak_gb", quiet) == 3.0


def test_tail_is_the_p95_of_every_request(outs):
    # nearest rank 19 of 20: the request that ended after the window
    for loop in ("open", "closed"):
        run = run_of(outs, loop)
        assert read("end_to_end", "p95_ms", run) == pytest.approx(500.0)
    assert read("layers", "gen_late_ms", run_of(outs, "closed")) is None


def test_a_split_metric_is_read_by_its_base_name(outs):
    run = run_of(outs, "closed")
    assert read("layers", "serve_ms.closed", run) == read(
        "layers", "serve_ms", run)
    with pytest.raises(FileNotFoundError):
        read("layers", "no_such_metric.closed", run)



def test_span_readers_find_nothing_without_spans(outs):
    run = run_of(outs, "open")
    for name in ("plan_ms", "fused_ms", "cast_ms", "engine_ms",
                 "idle_share", "haar_us"):
        assert read("layers", name, run) is None
