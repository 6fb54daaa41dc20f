"""Run a cell's lower-precision control on the chip, seed by seed.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 [--calls 4]

For each seed, in one process: the cell's set-up, then `--calls` answers
made by the control in the program's place (the driver's `control`: the
plain reference in bfloat16, or the program's own unrefined path),
compared with the plain reference as a run compares the
program's answers.  Each seed prints one JSON line with every compared
number beside its limit; a sound limit makes every control run
incorrect.  The benchmark's runs never run the control.
"""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 1,2,3")
    ap.add_argument("--calls", type=int, default=4)
    args = ap.parse_args(argv)
    from chipbench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        found = harness.run_control(args.workload, seed, args.calls)
        if found is None:
            return 1
        correct, checks = found
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct,
                          "checks": {n: {"value": harness.jsonable(v),
                                         "limit": lim}
                                     for n, v, lim in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
