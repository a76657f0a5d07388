"""Spans and round counters of the served path (``repro.telemetry.span``).

* Under a CPU profiler session every span of the engine and the pump is
  recorded, nested as docs/TELEMETRY.md says, with its ``round`` or
  ``ticket`` id.
* On a fake clock the stage walls and the queue wait are exactly the
  time the clock advanced in each stage; live work never exceeds the
  work executed; compiles are counted on a fresh shape and not on a warm
  one; the bank counts one hit or one load per kernel group planned.
"""

import threading
import time

import jax
import numpy as np
import pytest

from repro.core.isa import IM_DEPTH
from repro.core.overlay import compile_program
from repro.core.paper_bench import benchmark
from repro.launch.serve import OverlayServer
from repro.sched import AutoPump
from repro.telemetry import InMemorySink, check_stats

NAMES = ("poly5", "poly6", "chebyshev", "mibench")


@pytest.fixture(scope="module")
def kernels():
    return {n: compile_program(benchmark(n)) for n in NAMES}


def _xs(kernel, n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.uniform(-2, 2, (n,)).astype(np.float32)
            for _ in kernel.dfg.inputs]


def _words(kernel):
    return sum(len(img.words) for img in kernel.program.images)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------- the profiler trace
def _host_spans(trace_dir):
    """``[(thread, name, start_ns, end_ns, ids)]`` of the program's spans."""
    import glob
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "pump.")):
                    out.append((line.name, e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


def _inside(child, parents, same_id=None):
    """``child`` lies within one of ``parents`` on its thread (with the
    same value of the ``same_id`` stat, when given)."""
    thread, _, s, e, ids = child
    return any(p[0] == thread and p[2] <= s and e <= p[3]
               and (same_id is None or p[4].get(same_id) == ids[same_id])
               for p in parents)


def test_served_path_spans_on_the_profiler_trace(kernels, tmp_path):
    """The benchmark's traced run records host spans at level 1: every
    span of the served path appears there, nested, with its ids."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    srv = OverlayServer(bank_capacity=2)
    started = threading.Event()

    def hold_the_tick():          # a work item: runs under the pump lock
        started.set()
        time.sleep(0.5)
        return "held"

    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with AutoPump(srv) as pump:
            held = pump.submit_work(hold_the_tick)
            assert started.wait(10)
            tickets = [pump.submit(k, _xs(k, 300, i), tenant=f"t{i % 2}")
                       for i, k in enumerate(kernels.values())]
            for t in tickets + [held]:
                pump.result(t, timeout=60)
            stats = pump.stats()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    by = {}
    for sp in spans:
        by.setdefault(sp[1], []).append(sp)
    assert set(by) == {
        "pump.tick", "pump.lock_wait", "engine.submit", "engine.form_round",
        "engine.launch_round", "engine.plan", "engine.assemble",
        "engine.launch", "engine.retire", "engine.device_wait",
        "engine.collect"}
    # one request's spans share its ticket; the client thread waited for
    # the lock the work item held, and the wait was counted
    assert sorted(sp[4]["ticket"] for sp in by["engine.submit"]) == \
        sorted(tickets)
    assert {sp[0] for sp in by["pump.lock_wait"]} == \
        {sp[0] for sp in by["engine.submit"]}
    assert stats["submit_wait_s"] > 0.05
    # stages nest in their round's spans, rounds in the pump's ticks
    for name in ("engine.plan", "engine.assemble", "engine.launch"):
        assert all(_inside(sp, by["engine.launch_round"], "round")
                   for sp in by[name]), name
    for name in ("engine.device_wait", "engine.collect"):
        assert all(_inside(sp, by["engine.retire"], "round")
                   for sp in by[name]), name
    for name in ("engine.form_round", "engine.launch_round",
                 "engine.retire"):
        assert all(_inside(sp, by["pump.tick"]) for sp in by[name]), name
    # every kernel round launched was retired, under the same id
    launched = {sp[4]["round"] for sp in by["engine.launch_round"]}
    assert launched and launched <= {sp[4]["round"]
                                     for sp in by["engine.retire"]}


def test_a_submit_that_waits_for_the_lock_counts_its_wait(kernels):
    srv = OverlayServer(bank_capacity=2)
    started = threading.Event()

    def hold_the_tick():
        started.set()
        time.sleep(0.5)
        return "held"

    with AutoPump(srv) as pump:
        k = kernels["poly5"]
        pump.result(pump.submit(k, _xs(k, 64)), timeout=60)
        assert pump.stats()["submit_wait_s"] < 0.1
        pump.submit_work(hold_the_tick)
        assert started.wait(10)
        pump.result(pump.submit(k, _xs(k, 64)), timeout=60)
        s = pump.stats()
    check_stats("engine", s)
    assert 0.1 < s["submit_wait_s"] < 10


# ---------------------------------------------------------- fake-clock walls
def test_stage_walls_and_queue_wait_on_a_fake_clock(kernels, monkeypatch):
    """Each stage advances the fake clock by its own amount; the walls
    read exactly that, and each request's queue wait is its round's
    launch minus its submit."""
    clock = FakeClock()
    srv = OverlayServer(bank_capacity=4, round_kernels=1, max_inflight=1,
                        clock=clock)
    ov = srv.overlay

    def advancing(fn, dt):
        def run(*a, **kw):
            clock.advance(dt)
            return fn(*a, **kw)
        return run

    monkeypatch.setattr(ov, "plan", advancing(ov.plan, 1.0))
    monkeypatch.setattr(ov, "assemble", advancing(ov.assemble, 2.0))
    monkeypatch.setattr(ov, "execute", advancing(ov.execute, 4.0))
    monkeypatch.setattr(jax, "block_until_ready",
                        advancing(jax.block_until_ready, 8.0))
    monkeypatch.setattr(ov, "collect", advancing(ov.collect, 16.0))
    tickets = []
    for i, name in enumerate(("poly5", "poly6", "chebyshev")):
        tickets.append(srv.submit(kernels[name], _xs(kernels[name], 200, i)))
        clock.advance(0.5)
    srv.flush()
    s = srv.stats()
    assert s["rounds"] == 3
    assert s["stage_walls"] == {"plan_s": 3.0, "assemble_s": 6.0,
                                "launch_s": 12.0, "device_wait_s": 24.0,
                                "collect_s": 48.0}
    # rounds launch at 1.5 + 7, then 31 apart; submits at 0, 0.5, 1.0
    steps = srv.telemetry.steps()
    launches = sorted(row["t"] - row["wall_s"] for row in steps)
    assert launches == [8.5, 39.5, 70.5]
    by_round = {row["step"]: row["t"] - row["wall_s"] for row in steps}
    want = sum(by_round[srv.record(t)["round"]] - srv.record(t)["t_submit"]
               for t in tickets)
    assert s["queue_wait_s"] == pytest.approx(want) == 117.0
    events = srv.telemetry.events("deliver")
    assert sorted(e["ticket"] for e in events) == tickets
    assert sum(e["queue_s"] for e in events) == pytest.approx(117.0)
    # delivery comes a device wait and a collect after the launch
    assert all(e["latency_s"] == e["queue_s"] + 24.0 for e in events)


def test_flush_sync_delivers_queue_waits_too(kernels):
    clock = FakeClock()
    srv = OverlayServer(bank_capacity=4, clock=clock)
    k = kernels["poly5"]
    srv.submit(k, _xs(k, 64))
    clock.advance(2.5)
    srv.flush_sync()
    assert srv.stats()["queue_wait_s"] == 2.5
    (ev,) = srv.telemetry.events("deliver")
    assert ev["queue_s"] == 2.5 and ev["ticket"] == 0


# ------------------------------------------------------ live against executed
def test_live_and_executed_tiles_and_slots(kernels):
    srv = OverlayServer(bank_capacity=4, tile=128)
    a, b = kernels["poly5"], kernels["chebyshev"]
    srv.submit(a, _xs(a, 300))            # 3 tiles
    srv.submit(b, _xs(b, 1000))           # 8 tiles: a round of 11, pad 16
    srv.flush()
    s = srv.stats()
    assert s["rounds"] == 1
    assert (s["tiles_live"], s["tiles_executed"]) == (11.0, 16.0)
    assert s["slots_live"] == 300 * _words(a) + 1000 * _words(b)
    assert s["slots_executed"] == 16 * 128 * srv.overlay.s_max * IM_DEPTH
    assert 0 < s["slots_live"] <= s["slots_executed"]
    check_stats("engine", s)


def test_compiles_counted_on_a_fresh_shape_and_not_on_a_warm_one(kernels):
    # a tile width no other test serves, so the executor's shape is fresh
    srv = OverlayServer(bank_capacity=2, tile=72)
    k = kernels["poly6"]
    srv.submit(k, _xs(k, 500))
    srv.flush()
    first = srv.stats()
    assert first["compiles"] >= 1 and first["compile_s"] > 0
    srv.submit(k, _xs(k, 500, seed=1))
    srv.flush()
    second = srv.stats()
    assert second["compiles"] == first["compiles"]
    assert second["compile_s"] == first["compile_s"]
    assert [r["compiles"] for r in srv.telemetry.steps()] == \
        [first["compiles"], 0]


# ------------------------------------------------------------ the bank's hits
def test_bank_hits_plus_loads_equal_the_kernel_groups_planned(kernels):
    """Every kernel group a round plans is one hit or one load; a
    prefetch of a resident context (a router warming a replica) is
    neither."""
    srv = OverlayServer(bank_capacity=2, round_kernels=2)
    groups = []
    plan = srv.overlay.plan

    def counting(*a, **kw):
        p = plan(*a, **kw)
        groups.append(p.n_kernels)
        return p

    srv.overlay.plan = counting
    names = list(kernels)
    rng = np.random.RandomState(0)
    for wave in range(6):
        for i in rng.randint(0, len(names), 5):
            k = kernels[names[i]]
            srv.submit(k, _xs(k, 100, wave), tenant=f"t{i % 2}")
        srv.flush()
        resident = [k for k in kernels.values() if k in srv.bank]
        srv.bank.prefetch(resident)
    s = srv.stats()
    assert s["evictions"] > 0 and s["hits"] > 0
    assert s["hits"] + s["loads"] == sum(groups)


def test_telemetry_sink_spans_use_the_engines_sink(kernels):
    """The stage counters land in the sink the engine is built with."""
    sink = InMemorySink()
    srv = OverlayServer(bank_capacity=2, telemetry=sink)
    k = kernels["poly5"]
    srv.submit(k, _xs(k, 64))
    srv.flush()
    names = set(sink.counters("engine."))
    assert {"engine.plan_s", "engine.assemble_s", "engine.launch_s",
            "engine.device_wait_s", "engine.collect_s",
            "engine.queue_wait_s", "engine.tiles_live",
            "engine.slots_executed"} <= names
    assert "engine.execute_s" not in names
