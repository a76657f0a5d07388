"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload faas64.steady --seed 7 \\
        --seconds 20 --trace 0

Runs on the machine it is started on and needs the TPU chips the cell
asks for: without them it exits non-zero and prints no result.  The last
line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window), ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``: each number the check compared, beside its limit.  The same
numbers are the last lines of stderr.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench import tracefile as tracing  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(run: harness.Run, entries: list, compared: dict,
                failed: int) -> dict:
    metrics = {k: v for k, v in harness.read_metrics(run, entries).items()
               if math.isfinite(v["value"])}
    out = {"correct": harness.verdict(compared), "attempted": len(run.reqs),
           "failed": failed, "metrics": metrics, "device": dict(run.device)}
    if run.trace is not None:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": tracing.top_ops(run.trace),
                            "idle_gaps": run.trace["gaps"]}
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the trace events the reduction reads "
                         "to this path (gzipped JSON lines)")
    args = ap.parse_args(argv)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    try:
        cell = harness.load_cell(args.workload)
        run = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS, log=log,
                               trace_dir=trace_dir)
        if args.trace:
            events = tracing.load(tracing.find_xplane(trace_dir))
            if args.keep_trace:
                tracing.save_events(args.keep_trace, events)
            run.trace = tracing.reduce(events,
                                       max_s=min(harness.TRACE_S, args.seconds),
                                       chips=cell.chips)
            log(f"trace: {run.trace['window_s']:.3f} s traced, device busy "
                f"{run.trace['busy_s']:.3f} s, last device op ends at "
                f"{run.trace['device_end_s']:.3f} s; {run.trace['chips']} of "
                f"{cell.chips} chips ran an op")
            for chip, busy in run.trace["chip_busy_s"].items():
                log(f"trace: {chip} busy {busy:.6f} s, idle "
                    f"{100 * (1 - busy / run.trace['window_s']):.4f} %")
    except harness.BenchError as e:
        log(f"chipbench: {e}")
        return 1
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    compared, failed = harness.check_numbers(run)
    out = result_line(run, cell.layer_metrics if args.trace
                      else cell.metrics, compared, failed)
    for name, v in compared.items():
        log(f"compared {name} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
