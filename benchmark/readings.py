"""The readings that the limits of ``correct`` are set from, in one process.

    python3 benchmark/readings.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...] [--wrap <name>[@<s>] ... --wrap-seeds <n> ...]

Runs the cell once per seed as the benchmark does (set-up, window, the
comparison with the reference), first the program on ``--seeds``, then the
program with each ``--wrap`` (a name in ``faults.WRAPS``, with its own
window length after an ``@``) in the place of the chain's batch verifier
on ``--wrap-seeds``, and prints one JSON line per run with every number
compared. The compiled programs are shared by the runs, so a dozen seeds
cost one compile. The benchmark's own runs never load this file.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--wrap", action="append", default=[])
    ap.add_argument("--wrap-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    sys.path[0] = ROOT
    from benchmark import faults, harness

    harness.use_checkout_cache()

    runs = [(None, args.seconds, s) for s in args.seeds]
    for w in args.wrap:
        name, _, seconds = w.partition("@")
        runs += [(name, float(seconds or args.seconds), s)
                 for s in args.wrap_seeds]
    for wrap, seconds, seed in runs:
        t0 = time.monotonic()
        try:
            res = harness.run_cell(args.workload, seed, seconds, False, t0,
                                   wrap=faults.WRAPS[wrap] if wrap else None)
        except harness.NotAMeasurement as e:
            print(json.dumps({"wrap": wrap, "seed": seed, "error": str(e)}),
                  flush=True)
            continue
        print(json.dumps({
            "wrap": wrap, "seconds": seconds, "seed": seed,
            "correct": res["correct"],
            "checks": res["checks"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "seconds_total": time.monotonic() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
