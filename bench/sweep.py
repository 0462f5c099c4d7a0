"""Find a cell's knee: the highest open-loop rate whose completions keep
pace with its arrivals.  One set-up, then one window per offered rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 30 \
        --rates 4,6,8,10 [--out <file.json>]

For each rate it prints the offered and completed requests, p50 and p95 in
milliseconds, and whether the rate was sustained: every request answered,
and at least 95% of them inside the window.  The traffic mix's own rate is
not used.  Used once, when a cell's rate is chosen; the benchmark's runs do
not run it.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import loadgen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    if cell.traffic["loop"] != "open":
        print("sweep: the cell's traffic is not an open loop",
              file=sys.stderr)
        return 2
    try:
        prep = harness.prepare(cell, args.seed, rehearse=args.rehearse)
    except harness.NoChip as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 2
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = {**cell.traffic, "rate_per_s": rate}
        warm_rng, rng = harness.seeded_streams(args.seed + i, 2)
        harness.warm_up(prep, traffic, warm_rng)
        win, _, compiles = harness.measure(prep, traffic, args.seconds, rng)
        lat = win.latencies()
        n = len(win.outcomes)
        row = {"rate_per_s": rate, "offered": n,
               "completed_in_window": win.completed_in_window(),
               "failed": win.failed, "compiles": compiles,
               "p50_ms": 1e3 * loadgen.percentile(lat, 50),
               "p95_ms": 1e3 * loadgen.percentile(lat, 95),
               "max_ms": 1e3 * max(lat)}
        row["sustained"] = (win.failed == 0 and
                            row["completed_in_window"] >= 0.95 * n)
        rows.append(row)
        print(json.dumps(row), flush=True)
    prep.system.close()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
