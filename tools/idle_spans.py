"""One traced benchmark run, with the device's idle time split by the
program's own host spans.

    python tools/idle_spans.py --root . --workload faas64.steady \\
        --seed 7 --seconds 51 --out artifacts/idle.json

Runs ``chipbench/run.py --trace 1`` of the checkout at ``--root`` in this
process (its result line is printed as the benchmark prints it), with
one difference: the trace reduction keeps the program's host spans
(``engine.*``, ``pump.*``) beside the benchmark's ``bench.*`` ones, so
the ``breakdown``'s idle gaps are named by the program stage the host
was in.  Busy time, op and module seconds read as without them.  Then it
writes to ``--out`` the share of the traced idle time during which each
host span was open, by name, and during which any ``engine.*`` span,
only ``bench.wait`` (no other span but the window) and no span at all
were open, beside the traced run's wall time (process start to
the result line) and its end-to-end metrics.  A checkout whose program
has no spans reads 0 for the program's families.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()


def _union(ivs):
    out = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _intersect(a, b):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(ivs):
    return sum(e - s for s, e in ivs)


def idle_shares(events, max_s: float) -> dict:
    """Share of the traced window's idle time (one chip) during which
    each host span was open, by name, and the families of the module
    docstring; ``idle_s`` is the idle time itself."""
    from chipbench import tracefile
    win = [e for e in events if e.name == tracefile.WINDOW_SPAN][0]
    t0 = win.start_ns
    t1 = min(win.end_ns, t0 + max_s * 1e9)
    busy = _union([(max(e.start_ns, t0), min(e.end_ns, t1)) for e in events
                   if e.line == tracefile.OPS_LINE
                   and e.end_ns > t0 and e.start_ns < t1])
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    idle = [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]
    host = [e for e in events if e.plane.startswith("/host:")
            and e.name != tracefile.WINDOW_SPAN]

    def spans(pred):
        return _union([(e.start_ns, e.end_ns) for e in host if pred(e.name)])

    total = _length(idle)

    def share(pred):
        return _length(_intersect(idle, spans(pred))) / total

    out = {name: share(lambda n, name=name: n == name)
           for name in sorted({e.name for e in host})}
    out["engine.*"] = share(lambda n: n.startswith("engine."))
    waits = _intersect(idle, spans(lambda n: n == "bench.wait"))
    out["bench.wait only"] = (_length(waits) - _length(_intersect(
        waits, spans(lambda n: n != "bench.wait")))) / total
    out["no span"] = 1.0 - share(lambda n: True)
    out["idle_s"] = total / 1e9
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=".")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    root = Path(args.root).resolve()
    sys.path[:0] = [str(root), str(root / "src")]
    from chipbench import harness, run, tracefile

    keep, load, run_cell = tracefile._keep, tracefile.load, harness.run_cell
    seen = {}

    def keep_program_spans(plane, line, name):
        return keep(plane, line, name) or (
            plane.startswith("/host:") and name.startswith(("engine.",
                                                            "pump.")))

    def load_and_keep(path):
        seen["events"] = load(path)
        return seen["events"]

    def run_and_keep(*a, **kw):
        seen["run"] = run_cell(*a, **kw)
        return seen["run"]

    tracefile._keep, tracefile.load = keep_program_spans, load_and_keep
    harness.run_cell = run_and_keep
    run.T_PROCESS = T_PROCESS
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    wall_s = time.perf_counter() - T_PROCESS
    if rc != 0:
        return rc
    r = seen["run"]
    out = {"wall_s": wall_s,
           "traced_end_to_end": {
               k: v["value"] for k, v in
               harness.read_metrics(r, r.cell.metrics).items()},
           "idle_shares": idle_shares(
               seen["events"], min(harness.TRACE_S, args.seconds))}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
