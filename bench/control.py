#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the reference put
in the program's place with keys cut to 32 bits must come out not correct.

For each seed this runs the cell as the benchmark does (set-up, a short
window at the cell's own load, the read-back), compares the program's
answers with the reference (the lower reading: mismatched lanes of a sound
run) and then the 32-bit reference's answers to the same lanes (the upper
reading).  All seeds run in one process, so only the first compiles.

    python3 bench/control.py --workload ycsb-a.zipf.1chip --seconds 10 \\
        --seeds 11 12 13

Prints one JSON line per seed and exits non-zero without a TPU.  The
benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import harness
    import reference

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = harness.load_cell(args.workload)
        devices = harness.cell_devices(cell.chips)
    except harness.NoChip as e:
        log(f"control: {e}")
        return 2
    t_start = T_START
    for seed in args.seeds:
        run = harness.run(cell, devices, seed, args.seconds, False,
                          t_start=t_start, log=log)
        got = reference.control_answers(run.keys, run.log)
        ctl = reference.compare(run.keys, run.log, got)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "lanes": int(run.log.opc.size),
            "program_mismatched":
                run.result["checks"]["mismatched_lanes"]["value"],
            "control_mismatched": ctl["mismatched"],
            "control_by_op": ctl["by_op"],
            "correct": run.result["correct"],
            "control_correct": ctl["mismatched"] == 0,
        }), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
