"""Readings of the four-replica pool path, ``connect(processes=4)``, which
the benchmark does not measure as a cell yet: the pool's master stays off
JAX, so a run cannot read a chip's trace or its peak memory (PERF.md, Open
questions).

    python3 bench/pool_probe.py --seed <n> --seconds 20 \
        [--processes 4] [--clients 8] [--rehearse]

One process makes the mimic2-hemo cohort from the seed at the
configuration's sizes, starts the pool with the waveforms and the probes
registered as numpy arrays (each worker holds the whole table), trains and
warms each probe's query, runs a closed loop of ``--clients`` clients
through ``QueryServer.submit``, closes the pool and checks every answer
with the plain reference.  It prints one JSON line: the phases' seconds,
the workers' devices, the window's requests, qps, p50 and p95, and each
number compared beside its limit.  ``--rehearse`` uses the rehearsal sizes.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import refs  # noqa: E402

CONFIG = os.path.join(harness.BENCH, "configs", "mimic2-hemo.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--processes", type=int, default=4)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    harness.configure_jax_env()
    from repro.core import DenseTensor, connect

    cfg = harness.load_json(CONFIG)
    if args.rehearse:
        cfg.update(cfg["rehearsal"])
    n, p, levels = cfg["patients"], cfg["probes"], cfg["levels"]
    phases = {}
    t0 = time.perf_counter()
    s_waves = datagen.seed_sequence(args.seed, 1).spawn(3)[0]
    waves, _ = datagen.ecg_waveforms(n + p, cfg["samples"], s_waves,
                                     cfg["deterioration_frac"])
    probe_hist = refs.wave_hist(waves[n:], levels,
                                cfg["nbins"]).astype(np.float32)
    waves = waves[:n]
    phases["data"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    with connect(processes=args.processes, **cfg["connect"]) as s:
        s.register("waves", DenseTensor(waves), engine="dense_array")
        for i in range(p):
            s.register(f"probe_{i}", DenseTensor(probe_hist[i:i + 1]),
                       engine="dense_array")
        devices = s.bigdawg.devices()
        phases["pool_start"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        arr = s.islands.array
        queries = [arr.knn(arr.tfidf(arr.bin_hist(
            arr.haar("waves", levels=levels), nbins=cfg["nbins"],
            levels=levels)), f"probe_{i}", k=cfg["k"]) for i in range(p)]
        for q in queries:
            s.execute(q, mode="training")
        server = s.server()

        def serve(v):
            rep = server.submit(queries[v])
            return rep, np.asarray(rep.result.data)

        warm_rng, win_rng = harness.seeded_streams(args.seed, 2)
        traffic = {"loop": "closed", "clients": args.clients, "variants": p}
        warm = loadgen.drive(traffic, serve, loadgen.WARM_S, warm_rng)
        phases["train_warm"] = time.perf_counter() - t0
        setup_s = time.perf_counter() - T_START
        win = loadgen.drive(traffic, serve, args.seconds, win_rng)
    modes = {}
    for o in win.outcomes:
        if o.ok:
            modes[o.report.mode] = modes.get(o.report.mode, 0) + 1

    scores = (refs.tfidf(refs.wave_hist(waves, levels, cfg["nbins"]))
              @ probe_hist.astype(np.float64).T)
    gap, bad = 0.0, 0
    for o in win.outcomes:
        if not o.ok:
            continue
        idx = o.answer.ravel()
        if not refs.knn_answer_ok(idx, n, cfg["k"]):
            bad += 1
            continue
        gap = max(gap, refs.knn_gaps(scores[:, o.variant], idx, cfg["k"]))
    lim = cfg["limits"]
    checks = {"unanswered": [win.failed, 0],
              "knn_gap": [gap, lim["knn_gap"]],
              "bad_answers": [bad, lim["bad_answers"]]}
    lat = win.latencies()
    print(json.dumps({
        "seed": args.seed, "processes": args.processes,
        "clients": args.clients, "patients": n, "phases": phases,
        "setup_s": setup_s, "devices": devices,
        "warm": {"requests": len(warm.outcomes), "failed": warm.failed},
        "attempted": len(win.outcomes), "failed": win.failed,
        "modes": modes, "qps": win.completed_in_window() / args.seconds,
        "p50_ms": 1e3 * loadgen.percentile(lat, 50),
        "p95_ms": 1e3 * loadgen.percentile(lat, 95),
        "errors": sorted({o.error for o in win.outcomes if o.error})[:3],
        "correct": all(v <= lim for v, lim in checks.values()),
        "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
