"""A cell (a traffic file and a manifest entry) and a per-layer metric (a
reader file and a manifest entry) dropped into a copy of the benchmark are
found by name and run, with no code edited."""
import json
import os
import shutil

import harness

ROOT = os.path.dirname(harness.BENCH)

SERVED_COUNT = '''"""How many requests the window served."""


def read(run):
    return sum(1 for o in run.window.outcomes if o.ok)
'''


def test_new_cell_and_metric_are_found_by_name(tmp_path, monkeypatch):
    import loadgen
    monkeypatch.setattr(loadgen, "WARM_S", 0.5)
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    (bench / "traffic" / "knn-trickle.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 3.0, "variants": 2}))
    (bench / "layers" / "served_count.py").write_text(SERVED_COUNT)
    man["workloads"].append({"name": "hemo.knn-trickle",
                             "config": "mimic2-hemo",
                             "traffic": "knn-trickle", "chips": 1,
                             "why": "a test cell"})
    man["per_layer"].append({"name": "served_count", "unit": "requests",
                             "better": "higher", "source": "host_clock",
                             "layer": "front door", "moves": "p95_ms",
                             "workloads": ["hemo.knn-trickle"]})
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(man))

    cell = harness.resolve("hemo.knn-trickle", str(manifest), str(bench))
    assert cell.traffic["rate_per_s"] == 3.0
    assert cell.config["name"] == "mimic2-hemo"
    assert [m["name"] for m in cell.per_layer] == ["served_count"]
    # the latencies and qps name the cells they are reported in; the new
    # cell is not one
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    res = harness.run(cell, seed=11, seconds=1.0, trace=True,
                      rehearse=True)
    assert res["correct"]
    assert res["metrics"]["served_count"] == {"value": float(
        res["attempted"]), "unit": "requests"}
