"""The readings that a cell's correctness limits are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds 10 [--out <file.json>]

For each seed: one run of the cell as the benchmark makes it (set-up, a
window at the cell's own load, the plain reference's check), which gives
the program's reading of every number compared; and, for the control
seeds, the control: the reference put in the program's place in the next
lower precision (each configuration's ``control``), checked the same way.
The lower reading of a number is the largest the program gives, the upper
the smallest the control gives.  The benchmark's own runs never run the
control.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in seeds:
        try:
            res = harness.run(cell, seed, args.seconds, False,
                              rehearse=args.rehearse, keep_host=True)
        except harness.NoChip as exc:
            print(f"control: {exc}", file=sys.stderr)
            return 2
        row = {"seed": seed, "correct": res["correct"],
               "attempted": res["attempted"], "failed": res["failed"],
               "program": {k: c["value"] for k, c in res["checks"].items()}}
        if seed in control_seeds:
            t = time.perf_counter()
            answers = cell.config_mod.control(res["_cfg"], res["_host"])
            checks = cell.config_mod.reference(res["_cfg"], res["_host"],
                                               answers)
            row["control"] = {k: v for k, (v, _) in checks.items()}
            row["control_seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
        del res
    names = rows[0]["program"].keys()
    summary = {
        "lower": {k: max(r["program"][k] for r in rows) for k in names},
        "upper": {k: min(r["control"][k] for r in rows if "control" in r)
                  for k in names if any(k in r.get("control", {})
                                        for r in rows)}}
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows, **summary},
                      f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
