#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload ycsb-a.zipf.1chip --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration, its traffic and its metrics are read from
``BENCHMARK.json`` and the files it names.  With ``--trace 0`` the result
line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  Exits non-zero
and prints no result unless JAX finds a TPU with as many chips as the cell
asks for.  The last lines on standard error, and the ``checks`` key that
ends the result line, give each number compared with the reference beside
its limit.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
