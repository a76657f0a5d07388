"""Find a configuration's knee: the highest offered rate at which the
queue does not grow through the window.

    python3 chipbench/sweep.py --workload faas64.steady --seed 11 \\
        --seconds 10 --rates 50,100,200,300

One process sets up once, warms up on the cell's traffic, then offers
each rate in turn for ``--seconds`` (the cell's mix with only
``rate_rps`` changed), drains, and prints one JSON line per rate:
requests due, delivered inside the window, the backlog at the window's
close (requests due by then and not yet claimed), p50/p95 latency of the
requests due in each half of the window, the largest round in tiles, and
compiles in the window.  It stops after the first rate whose backlog does
not drain within a minute.  Needs the cell's TPU chips.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, req/s, ascending")
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = harness.load_cell(args.workload)
        rig = harness.Rig(cell, args.seed, log=log)
    except harness.BenchError as e:
        log(f"chipbench: {e}")
        return 1
    try:
        n = rig.warm_up()
        log(f"warm-up: {n} requests; set-up {time.perf_counter() - T_PROCESS:.1f} s")
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate_rps=rate)
            c0, r0 = rig.clock.mark(), len(rig.round_tiles())
            reqs, start, out = rig.phase(traffic, 100 + i, args.seconds)
            end = start + args.seconds
            compiles = rig.clock.summary(c0)
            rounds = rig.round_tiles()[r0:]
            rig.gen.drain(out, end + harness.DRAIN_S)
            backlog = sum(1 for r in reqs if start + r.due <= end
                          and (r.t_claim is None or r.t_claim > end))
            half = start + args.seconds / 2
            lat = {}
            for part, sel in (("first", [r for r in reqs if start + r.due < half]),
                              ("second", [r for r in reqs if start + r.due >= half])):
                ls = np.array([r.t_claim - (start + r.due) for r in sel
                               if r.t_claim is not None]) * 1e3
                lat[part] = ([float(np.percentile(ls, 50)),
                              float(np.percentile(ls, 95))] if ls.size else None)
            inside = [r for r in reqs if r.t_claim is not None and r.t_claim <= end]
            print(json.dumps({
                "rate_rps": rate, "due": len(reqs),
                "delivered_in_window": len(inside),
                "served_melem_per_s": sum(r.length for r in inside) / args.seconds / 1e6,
                "backlog_at_close": backlog,
                "p50_p95_ms_first_half": lat["first"],
                "p50_p95_ms_second_half": lat["second"],
                "undrained": len(out),
                "largest_round_tiles": max(rounds, default=0),
                "warmed_up_to": int(cell.traffic["warmup_tiles_up_to"]),
                "window_compiles": compiles}),
                flush=True)
            if out:
                break
            rig.pump.wait_idle(timeout=harness.DRAIN_S)
    finally:
        rig.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
