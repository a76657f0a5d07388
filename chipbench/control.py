"""Readings that set the limits of ``correct``: the program's and the
control's, over many seeds in one process.

    python3 chipbench/control.py --workload faas64.steady \\
        --seeds 1,2,3 --seconds 10

For each seed it makes one run of the cell as ``run.py`` does (set-up,
warm-up, a window of ``--seconds`` at the cell's own load, drain), prints
the numbers compared for the program's answers, then puts the control in
the program's place (every answer replaced by the reference computed in
bfloat16) and prints the same numbers for it.  One JSON line per seed.
Needs the cell's TPU chips; the benchmark's own runs do not run this.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            run = harness.run_cell(cell, seed, args.seconds, False,
                                   time.perf_counter(), log=log)
        except harness.BenchError as e:
            log(f"chipbench: {e}")
            return 1
        program, failed = harness.check_numbers(run)
        harness.put_control(run)
        control, c_failed = harness.check_numbers(run)
        print(json.dumps({
            "seed": seed, "requests": len(run.reqs),
            "program": {k: v["value"] for k, v in program.items()},
            "program_failed": failed,
            "control": {k: v["value"] for k, v in control.items()},
            "control_failed": c_failed,
            "control_correct": harness.verdict(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
