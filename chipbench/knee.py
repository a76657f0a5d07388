"""Pick the knee from ``sweep.py``'s lines and write the open-loop
traffic files' rates from it.

    python3 chipbench/knee.py sweep.out --write steady=0.8

The knee is the highest swept rate up to which the queue did not grow
through the window at any rate: at least 95 % of the requests due were
delivered inside it, the backlog at its close is at most half a second
of arrivals, the second half's median latency is at most twice the
first half's, and no round outgrew the sizes served in set-up (a larger
one compiles in the window, stalls the pump and lets the queue grow).  ``--write NAME=F`` sets ``rate_rps`` of
``chipbench/traffic/NAME.json`` to ``F`` times the knee and records the
knee beside it.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def held(r: dict) -> bool:
    first, second = r["p50_p95_ms_first_half"], r["p50_p95_ms_second_half"]
    return (r["undrained"] == 0
            and r["delivered_in_window"] >= 0.95 * r["due"]
            and r["backlog_at_close"] <= 0.5 * r["rate_rps"]
            and first is not None and second is not None
            and second[0] <= 2 * first[0]
            and r["largest_round_tiles"] <= r["warmed_up_to"])


def knee(rows: list[dict]) -> float:
    best = None
    for r in sorted(rows, key=lambda r: r["rate_rps"]):
        if not held(r):
            break
        best = r["rate_rps"]
    if best is None:
        raise SystemExit("no swept rate held the queue")
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("sweep_out")
    ap.add_argument("--write", action="append", default=[])
    args = ap.parse_args(argv)
    rows = [json.loads(line) for line in
            Path(args.sweep_out).read_text().splitlines() if line.strip()]
    k = knee(rows)
    print(json.dumps({"knee_rps": k}))
    for item in args.write:
        name, factor = item.split("=")
        path = HERE / "traffic" / f"{name}.json"
        t = json.loads(path.read_text())
        t["knee_rps"] = k
        t["rate_of_knee"] = float(factor)
        t["rate_rps"] = round(k * float(factor), 1)
        path.write_text(json.dumps(t, indent=1) + "\n")
        print(json.dumps({name: t["rate_rps"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
