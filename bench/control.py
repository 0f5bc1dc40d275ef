"""The control of the comparison that decides ``correct``.

The plain reference, computed in bfloat16 (one step below the float32
that every configuration states), is put in the program's place: for
each seed it answers the window's requests of a run at the cell's own
size and load, and the comparison judges those answers as it judges the
program's.  The comparison is sound only where every seed here comes
out not correct.

    python3 bench/control.py --workload paper-mqr-30k.nearest \
        --seeds 1,2,3 --seconds 30
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_checks(root, workload, seed, seconds, overrides=None):
    from bench import harness, reference

    cell = harness.load_cell(root, workload, overrides)
    inp = harness.make_inputs(cell, seed)
    reqs, _ = inp.window_requests(seconds)
    n = inp.data.shape[0]
    low = reference.Reference(inp.data, precision="bfloat16")
    answers = reference.control_answers(reqs, low, inp.k, n)
    return reference.compare(reqs, answers, reference.Reference(inp.data),
                             inp.k, cell.config["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    refused = True
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control_checks(ROOT, args.workload, seed, args.seconds)
        correct = all(v <= lim for _, v, lim in checks)
        refused &= not correct
        print(json.dumps({"seed": seed, "correct": correct, "checks": {
            name: {"value": v, "limit": lim} for name, v, lim in checks}}),
            flush=True)
    return 0 if refused else 1


if __name__ == "__main__":
    sys.exit(main())
