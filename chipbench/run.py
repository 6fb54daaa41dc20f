"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout: the program is imported from `src/`
beside this directory, and the cell from `BENCHMARK.json` there.  With
`--trace 0` the result line carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
first calls of the window.  Without a TPU, with fewer chips than the cell
asks for, or without the program beside it, the run exits non-zero and
prints no result.  `chipbench/harness.py` says what a run does.
"""
import time

T_PROCESS = time.perf_counter()     # setup_s is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    sys.exit(harness.main(t_process=T_PROCESS))
