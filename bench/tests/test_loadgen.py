"""The load generator: seeded schedules, latency from the due time, and
percentiles over every request with failed ones counted slowest."""
import threading
import time

import numpy as np
import pytest

import loadgen


def rng(seed):
    return np.random.default_rng(seed)


def test_same_seed_same_schedule():
    a = loadgen.open_schedule(7.0, 30.0, 4, rng(3000000019))
    b = loadgen.open_schedule(7.0, 30.0, 4, rng(3000000019))
    c = loadgen.open_schedule(7.0, 30.0, 4, rng(3000000020))
    assert a == b
    assert a != c


def test_every_seed_offers_the_same_work():
    for seed in (1, 2, 2**31 + 5):
        sched = loadgen.open_schedule(7.0, 30.0, 4, rng(seed))
        assert len(sched) == 210
        dues = [d for d, _ in sched]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 30.0
        counts = np.bincount([v for _, v in sched], minlength=4)
        assert counts.max() - counts.min() <= 1


def test_closed_sequences_are_seeded_and_balanced():
    a = loadgen.closed_sequences(4, 4, rng(9), length=64)
    assert a == loadgen.closed_sequences(4, 4, rng(9), length=64)
    for seq in a:
        for i in range(0, 64, 4):
            assert sorted(seq[i:i + 4]) == [0, 1, 2, 3]


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert loadgen.percentile(vals, 50) == 50
    assert loadgen.percentile(vals, 95) == 95
    assert loadgen.percentile([5.0], 95) == 5.0
    with pytest.raises(ValueError):
        loadgen.percentile([], 50)


def test_latency_runs_from_the_due_time():
    """One thread, two requests due 0.05 s apart, each taking 0.2 s: the
    second waits for the first, and its latency counts the wait."""
    def serve(v):
        time.sleep(0.2)
        return {"seconds": 0.2}, v

    win = loadgen.run_open(serve, [(0.0, 0), (0.05, 1)], seconds=0.1,
                           grace_s=5.0, threads=1)
    first, second = win.outcomes
    # handed over on time, taken up once the thread is free
    assert second.queued - second.due < 0.01
    assert second.sent - second.due == pytest.approx(0.15, abs=0.05)
    lat = win.latencies()
    assert lat[0] == pytest.approx(0.2, abs=0.05)
    assert lat[1] == pytest.approx(0.35, abs=0.05)
    assert win.failed == 0 and win.completed_in_window() == 0


def test_failed_requests_count_slowest():
    def serve(v):
        if v == 1:
            raise RuntimeError("engine down")
        return {"seconds": 0.0}, v

    win = loadgen.run_open(serve, [(0.0, 0), (0.0, 1), (0.01, 0)],
                           seconds=0.5, grace_s=1.0, threads=4)
    assert win.failed == 1
    bad = [o for o in win.outcomes if not o.ok][0]
    assert "engine down" in bad.error
    lat = win.latencies()
    assert max(lat) == pytest.approx(win.deadline - bad.due)
    assert loadgen.percentile(lat, 95) == max(lat)
    assert loadgen.percentile(lat, 50) < 0.5


def test_a_request_that_never_returns_is_failed_at_the_deadline():
    gate = threading.Event()

    def serve(v):
        if v == 1:
            gate.wait(10)
        return {"seconds": 0.0}, v

    t = time.perf_counter()
    win = loadgen.run_open(serve, [(0.0, 0), (0.0, 1)], seconds=0.2,
                           grace_s=0.3, threads=2)
    assert time.perf_counter() - t < 2.0
    gate.set()
    assert win.outcomes[1].ok is False or win.outcomes[1].done > 0.5


def test_closed_loop_waits_for_each_reply():
    active = []
    peak = []

    def serve(v):
        active.append(v)
        peak.append(len(active))
        time.sleep(0.02)
        active.pop()
        return {"seconds": 0.02}, v

    win = loadgen.drive({"loop": "closed", "clients": 2, "variants": 4},
                        serve, 0.3, rng(4))
    assert max(peak) <= 2
    assert win.loop == "closed" and win.failed == 0
    assert 0 < win.completed_in_window() <= len(win.outcomes)
    assert all(0 <= o.sent - o.due < 0.01 for o in win.outcomes)
