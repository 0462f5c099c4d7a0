"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a workload of ``BENCHMARK.json``.  The last line of standard
output is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, traced, ``breakdown``; its last key, ``checks``, holds each
number compared with the plain reference beside its limit, and the same
lines end standard error.  A run that finds no TPU, or fewer chips than the
cell asks for, prints no result and exits 2.  ``--rehearse`` runs the cell
at its configuration's rehearsal sizes on whatever JAX finds (the CPU
here), prints the would-be result to standard error and exits 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="rehearsal sizes, any backend; prints no result")
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             rehearse=args.rehearse, t_start=T_START)
    except harness.NoChip as exc:
        print(f"bench: {exc}: no result", file=sys.stderr, flush=True)
        return 2
    if args.rehearse:
        print("rehearsal, not a result: " + json.dumps(result),
              file=sys.stderr, flush=True)
        harness.print_checks(result)
        return 3
    print(json.dumps(result), flush=True)
    harness.print_checks(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
