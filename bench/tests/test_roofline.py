"""The roofline arithmetic against peaks.json, and the Haar readers."""
import json
import os
from types import SimpleNamespace

import pytest

import roofline
import trace_reduce as tr
from harness import reader, BENCH

V5E = "TPU v5 lite"


def test_peaks_table_has_the_v5e_and_its_source():
    with open(roofline.PEAKS_FILE) as f:
        table = json.load(f)
    pk = table[V5E]
    assert pk["flops_per_s"]["bf16"] == 1.97e14
    assert pk["hbm_bytes_per_s"] == 8.19e11
    assert pk["hbm_bytes"] == 1.6e10
    assert "TPU v5e" in pk["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    with pytest.raises(KeyError):
        roofline.least_seconds(1.0, 1.0, "cpu")


def test_haar_work_and_its_bound():
    ops, nbytes = roofline.haar_work(8192, 16384)
    assert ops == 4 * 8192 * 16384
    assert nbytes == 2 * 8192 * 16384 * 4
    t, bound = roofline.least_seconds(ops, nbytes, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(1073741824 / 8.19e11)       # 1.311 ms
    assert roofline.share_percent(ops, nbytes, 2 * t, V5E) == \
        pytest.approx(50.0)


def test_ops_bound_when_ops_dominate():
    t, bound = roofline.least_seconds(1.97e14, 1.0, V5E)
    assert (t, bound) == (pytest.approx(1.0), "ops")


def test_share_needs_a_positive_time():
    with pytest.raises(ValueError):
        roofline.share_percent(1.0, 1.0, 0.0, V5E)


def _run_with_haar(durations_ns):
    ops = [tr.Op("fusion.1", 0, 5e6)] + [
        tr.Op("haar_pallas", 10e6 * (i + 1), d)
        for i, d in enumerate(durations_ns)]
    profile = SimpleNamespace(ops=ops, window_ns=1e9, busy_ns=0.0)
    return SimpleNamespace(profile=profile, device_kind=V5E,
                           extra={"cfg": {"patients": 8192,
                                          "samples": 16384}})


def test_haar_readers_from_the_trace():
    run = _run_with_haar([2.5e6, 2.744e6])
    us = reader(BENCH, "layers", "haar_us")(run)
    assert us == pytest.approx(2622.0)
    share = reader(BENCH, "layers", "haar_roofline")(run)
    assert share == pytest.approx(100 * (1073741824 / 8.19e11) / 2.622e-3)


def test_haar_readers_find_nothing_without_the_kernel():
    run = _run_with_haar([])
    for name in ("haar_us", "haar_roofline"):
        assert reader(BENCH, "layers", name)(run) is None
    run.profile = None
    assert reader(BENCH, "layers", "haar_us")(run) is None
    assert os.path.isdir(BENCH)
