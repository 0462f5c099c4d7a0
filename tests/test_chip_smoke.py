"""``chip_smoke.py`` on the CPU backend at its rehearsal size: every phase
runs and passes its numpy-reference checks, and the script still refuses to
print a result off a TPU."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402


def test_small_phases_pass_the_numpy_reference_checks():
    report = chip_smoke.run_single(chip_smoke.SMALL, seed=0)
    checks = report["checks"]
    assert checks["haar_max_abs_err"] <= chip_smoke.HAAR_ATOL
    assert checks["matmul_max_abs_err"] <= chip_smoke.MATMUL_ATOL
    margins = checks["knn_min_margin"]
    assert {"training", "dense_only", "columnar_only", "hybrid",
            "served_0"} <= set(margins)
    assert min(margins.values()) >= -chip_smoke.KNN_RTOL
    for phase in ("analytic_training", "analytic_production",
                  "placement_columnar_only", "query_server", "cross_island"):
        assert report["phases"][phase]["seconds"] > 0
    for counters in report["counters"].values():
        assert counters["fusion_fallbacks"] == 0
        assert counters["ivm_fallbacks"] == 0
        assert counters["breaker_trips"] == 0


def test_refuses_a_result_off_tpu(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "run_single", lambda size, seed: {})
    assert chip_smoke.main(["--small"]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    # at full size it refuses before building anything
    monkeypatch.setattr(chip_smoke, "run_single", lambda size, seed: 1 / 0)
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_knn_check_rejects_a_wrong_neighbour():
    weights = np.eye(chip_smoke.K + 1)[:, :4]
    weights[:, 0] = np.arange(chip_smoke.K + 1)
    test_hist = np.array([1.0, 0.0, 0.0, 0.0])
    best = np.argsort(weights[:, 0])[::-1][:chip_smoke.K]
    assert chip_smoke.check_knn(best, weights, test_hist) >= 0.0
    wrong = best.copy()
    wrong[0] = 0                        # the lowest-scoring row
    with pytest.raises(chip_smoke.CheckFailed):
        chip_smoke.check_knn(wrong, weights, test_hist)


def test_hist_check_allows_only_bin_edge_ties():
    rng = np.random.default_rng(0)
    coeffs = rng.normal(size=(3, 256)).astype(np.float32)
    want, _ = chip_smoke.np_bin_hist(coeffs, chip_smoke.NBINS,
                                     chip_smoke.LEVELS)
    assert chip_smoke.check_hist(want, coeffs) == 0
    moved = want.copy()
    moved[0, 3] += 1                    # a count no tie can explain
    moved[0, 4] -= 1
    with pytest.raises(chip_smoke.CheckFailed):
        chip_smoke.check_hist(moved, coeffs)


def test_last_line_contract_on_tpu(monkeypatch, capsys):
    """On a TPU the last line is exactly the result object (the device as
    JAX reports it); steered here by pretending the backend is a TPU."""
    class FakeDevice:
        platform, device_kind = "tpu", "TPU v5 lite"
    import jax
    monkeypatch.setattr(chip_smoke, "run_single", lambda size, seed: {})
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDevice()])
    assert chip_smoke.main(["--small"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
