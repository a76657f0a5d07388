"""Reduce a profiler trace of the measured window to the benchmark's numbers.

The run wraps its window in a ``bench.window`` host span, and its own
calls into the server in ``bench.*`` spans
(``jax.profiler.TraceAnnotation``); the program's own host spans are
named ``engine.*`` and ``pump.*``.  ``reduce`` reads the device planes
(``/device:TPU:n``): their ``XLA Ops`` line gives when an operation ran,
their ``XLA Modules`` line which program it belonged to.  All times are
clipped to the window.

* ``busy_s``: the union of the op intervals, averaged over the cell's
  chips (a chip that ran no op in the window counts as idle); the idle
  share is ``1 - busy_s / window_s``.  ``chip_busy_s`` holds each busy
  chip's, by device plane; ``chips`` counts them.
* ``op_seconds`` / ``module_seconds``: clipped durations summed by name.
* ``gaps``: the intervals of the window in which a chip ran no op, each
  named by the host spans open at its midpoint, the benchmark's and the
  program's (``none`` when no span was open).
"""

from __future__ import annotations

import dataclasses
import glob
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the host spans kept: the benchmark's own, the engine's and the pump's
HOST_SPANS = ("bench.", "engine.", "pump.")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def _keep(plane: str, line: str, name: str) -> bool:
    if _is_device(plane):
        return line in (OPS_LINE, MODULES_LINE)
    return plane.startswith("/host:") and name.startswith(HOST_SPANS)


def load(path: str) -> list[Event]:
    """The events ``reduce`` reads: device ops and modules, and the
    benchmark's and the program's host spans."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if _keep(plane.name, line.name, e.name):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def save_events(path: str, events: list[Event]) -> None:
    """Write ``load``'s events as gzipped JSON (a test fixture): a table
    of strings, then one ``[plane, line, name, start_ns, dur_ns]`` row
    per event with the strings as indices into it."""
    import gzip
    import json
    strings: dict[str, int] = {}

    def idx(x):
        return strings.setdefault(x, len(strings))

    rows = [[idx(e.plane), idx(e.line), idx(e.name), e.start_ns, e.dur_ns]
            for e in events]
    with gzip.open(path, "wt") as f:
        json.dump({"strings": list(strings), "events": rows}, f)


def load_events(path: str) -> list[Event]:
    import gzip
    import json
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    st = data["strings"]
    return [Event(st[p], st[ln], st[n], float(s), float(d))
            for p, ln, n, s, d in data["events"]]


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(e: Event, t0: float, t1: float) -> tuple[float, float] | None:
    s, t = max(e.start_ns, t0), min(e.end_ns, t1)
    return (s, t) if t > s else None


def reduce(events: list[Event], n_gaps: int = 10,
           max_s: float | None = None, chips: int = 1) -> dict:
    """The numbers of the window, or of its first ``max_s`` seconds, on
    a cell of ``chips`` chips."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    t0 = windows[0].start_ns
    t1 = windows[0].end_ns
    if max_s is not None:
        t1 = min(t1, t0 + max_s * 1e9)
    window_s = (t1 - t0) / 1e9

    ops_by_chip: dict[str, list] = defaultdict(list)
    op_seconds: dict[str, float] = defaultdict(float)
    module_seconds: dict[str, float] = defaultdict(float)
    spans_by_thread: dict[str, list[Event]] = defaultdict(list)
    for e in events:
        if _is_device(e.plane):
            iv = _clip(e, t0, t1)
            if iv is None:
                continue
            if e.line == OPS_LINE:
                ops_by_chip[e.plane].append(iv)
                op_seconds[e.name] += (iv[1] - iv[0]) / 1e9
            else:
                module_seconds[e.name] += (iv[1] - iv[0]) / 1e9
        elif e.name != WINDOW_SPAN:
            spans_by_thread[e.line].append(e)

    busy, gaps = {}, []
    for chip, ivs in sorted(ops_by_chip.items()):
        merged = _union(ivs)
        busy[chip] = sum(e - s for s, e in merged) / 1e9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    named = []
    for length, s, e in gaps[:n_gaps]:
        mid = (s + e) / 2
        open_spans = sorted({sp.name for spans in spans_by_thread.values()
                             for sp in spans
                             if sp.start_ns <= mid < sp.end_ns})
        named.append(["+".join(open_spans) or "none", length / 1e9])
    return {
        "window_s": window_s,
        "busy_s": (sum(busy.values()) / max(chips, len(busy)) if busy
                   else 0.0),
        "chip_busy_s": busy,
        "chips": len(busy),
        # where the last device op ends: short of ``window_s`` by more
        # than an idle gap when the profiler dropped the window's end
        "device_end_s": max((iv[1] for ivs in ops_by_chip.values()
                             for iv in ivs), default=t0) / 1e9 - t0 / 1e9,
        "op_seconds": dict(op_seconds),
        "module_seconds": dict(module_seconds),
        "gaps": named,
    }


#: an op's name in the breakdown: its HLO text cut to this many characters
OP_NAME_CHARS = 120


def top_ops(summary: dict, n: int = 10) -> list:
    """The ``n`` device ops that took most time, by name (the HLO text,
    cut short; ops whose cut names agree are summed)."""
    tot: dict[str, float] = defaultdict(float)
    for name, s in summary["op_seconds"].items():
        tot[name[:OP_NAME_CHARS]] += s
    ops = sorted(tot.items(), key=lambda kv: -kv[1])
    return [[name, s] for name, s in ops[:n]]


def describe(path: str, n: int = 8) -> str:
    """Every plane and line of a trace, with event counts and the names
    that took most time: what to read before pointing a metric at it."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            tot: dict[str, float] = defaultdict(float)
            count = 0
            for e in line.events:
                tot[e.name] += e.duration_ns / 1e9
                count += 1
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
            out.append(f"  line {line.name!r}: {count} events; " + "; ".join(
                f"{name[:80]} {s:.6f}s" for name, s in top))
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys
    print(describe(sys.argv[1]))
    print(json.dumps(reduce(load(sys.argv[1])), indent=1))
