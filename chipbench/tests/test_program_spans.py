"""CPU tests of the readers of the program's own counters and spans
(queue wait, submit wait, device wait per round, live slot share), and of
the trace reduction with the program's host spans among its events."""

from pathlib import Path

import pytest

from chipbench import harness, tracefile
from chipbench.metrics import (device_wait_ms_per_round, live_slot_share,
                               queue_wait_ms, submit_wait_ms)

BENCH = Path(tracefile.__file__).resolve().parent
FIXTURE = BENCH / "tests" / "data" / "steady_v5e_1s.json.gz"


def stats(**kw):
    """A pump-wrapped engine's ``stats()`` of the keys the readers use."""
    walls = {"plan_s": 0.0, "assemble_s": 0.0, "launch_s": 0.0,
             "device_wait_s": kw.pop("device_wait_s", 0.0),
             "collect_s": 0.0}
    s = {"rounds": 0, "requests": 0, "submits": 0, "queue_wait_s": 0.0,
         "submit_wait_s": 0.0, "slots_live": 0.0, "slots_executed": 0.0,
         "stage_walls": walls}
    s.update(kw)
    return s


def run_of(stats0, stats1):
    return harness.Run(cell=None, specs=[], blocks=[], setup_s=0.0,
                       seconds=10.0, start=0.0, reqs=[], stats0=stats0,
                       stats1=stats1)


def test_readers_of_the_programs_counters():
    run = run_of(
        stats(rounds=100, requests=150, submits=150, queue_wait_s=1.0,
              submit_wait_s=0.5, slots_live=1e6, slots_executed=1e8,
              device_wait_s=2.0),
        stats(rounds=1100, requests=1650, submits=1660, queue_wait_s=4.0,
              submit_wait_s=2.0, slots_live=3e6, slots_executed=4.1e8,
              device_wait_s=4.5))
    assert queue_wait_ms.read(run) == pytest.approx(1e3 * 3.0 / 1500)
    assert submit_wait_ms.read(run) == pytest.approx(1e3 * 1.5 / 1510)
    assert device_wait_ms_per_round.read(run) == pytest.approx(2.5)
    assert live_slot_share.read(run) == pytest.approx(100 * 2e6 / 3.1e8)


@pytest.mark.parametrize("reader", [queue_wait_ms, submit_wait_ms,
                                    device_wait_ms_per_round,
                                    live_slot_share])
def test_readers_find_nothing_in_an_empty_window_or_an_older_program(reader):
    """No rounds, requests or slots in the window read nothing; so does a
    program whose ``stats()`` lacks the counters (the parent of the
    change that added them)."""
    idle = stats(rounds=5, requests=5, submits=5, slots_executed=10.0)
    assert reader.read(run_of(idle, dict(idle))) is None
    old = stats(rounds=5, requests=5, submits=5)
    for k in ("queue_wait_s", "submit_wait_s", "slots_live",
              "slots_executed"):
        del old[k]
    del old["stage_walls"]["device_wait_s"]
    grown = dict(old, rounds=50, requests=60, submits=60)
    assert reader.read(run_of(old, grown)) is None


def ev(plane, line, name, start, dur):
    return tracefile.Event(plane, line, name, float(start), float(dur))


def test_program_spans_only_name_the_gaps_of_a_chip_trace():
    """Host spans of the program among the events of a faas64.steady
    trace recorded on one v5e: busy time, op and module seconds and the
    gaps' lengths read as before; only the gaps' names can change."""
    events = tracefile.load_events(str(FIXTURE))
    host = [e for e in events if e.name.startswith("bench.pump_once")]
    assert host
    program = []
    for e in host:        # a pump tick around each traced pump_once,
        program.append(ev(e.plane, e.line, "pump.tick", e.start_ns - 10,
                          e.dur_ns + 20))
        # ... and a collect in its second half
        program.append(ev(e.plane, e.line, "engine.collect",
                          e.start_ns + e.dur_ns / 2, e.dur_ns / 2))
    before = tracefile.reduce(events)
    after = tracefile.reduce(events + program)
    for key in ("window_s", "busy_s", "chips", "device_end_s",
                "op_seconds", "module_seconds"):
        assert after[key] == before[key], key
    assert [g[1] for g in after["gaps"]] == [g[1] for g in before["gaps"]]
    renamed = [a[0] for a, b in zip(after["gaps"], before["gaps"])
               if a[0] != b[0]]
    assert all("pump.tick" in name for name in renamed)


def test_a_gap_is_named_by_the_program_spans_open_in_it():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        ev(host, "main", "bench.window", 0, 10000),
        ev(dev, "XLA Ops", "fusion", 0, 1000),
        ev(dev, "XLA Ops", "fusion", 6000, 4000),
        # the pump thread collects while a client waits for the lock
        ev(host, "pump", "pump.tick", 500, 5000),
        ev(host, "pump", "engine.retire", 900, 4000),
        ev(host, "pump", "engine.collect", 2000, 2500),
        ev(host, "main", "bench.submit", 2500, 3000),
        ev(host, "main", "pump.lock_wait", 2600, 2800),
    ]
    (gap,) = tracefile.reduce(events)["gaps"]
    assert gap == ["bench.submit+engine.collect+engine.retire+"
                   "pump.lock_wait+pump.tick", pytest.approx(5e-6)]
