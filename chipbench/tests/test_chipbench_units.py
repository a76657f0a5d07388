"""CPU tests of the benchmark's parts: traffic, catalog, reference, trace
reduction, and the harness's refusal to run without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import catalog, reference, tracefile
from chipbench import traffic as tr
from chipbench.metrics import executor_roofline

BENCH = Path(tracefile.__file__).resolve().parent
ROOT = BENCH.parent
SEED = 2 ** 31 + 12345         # larger than 32 signed bits hold


def load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def schedule(seed, stream=tr.STREAM_WINDOW, seconds=20.0):
    return tr.open_schedule(load("traffic", "steady"), 64, 16, 65536,
                            seconds, tr.rng_for(seed, stream))


def test_open_schedule_is_fixed_by_the_seed():
    a, b, c = schedule(SEED), schedule(SEED), schedule(SEED + 1)
    assert a == b
    assert a != c
    # every seed gets the same count and the same sets of sizes, kernels,
    # tenants and gaps, each in an order of its own
    assert len(a) == len(c)
    for key in ("length", "kernel", "tenant"):
        assert sorted(getattr(r, key) for r in a) == \
            sorted(getattr(r, key) for r in c)
        assert [getattr(r, key) for r in a] != [getattr(r, key) for r in c]
    q = (np.arange(len(a)) + 0.5) / len(a)
    gaps = -np.log1p(-q)
    gaps *= 20.0 / gaps.sum()
    for x in (a, c):
        d = np.diff([r.due for r in x])
        assert np.abs(d[:, None] - gaps[None, :]).min(axis=1).max() < 1e-9
    assert schedule(SEED, tr.STREAM_WARMUP) != a
    due = [r.due for r in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] <= 20.0
    lens = np.array([r.length for r in a])
    assert lens.min() >= 128 and lens.max() <= 65536
    assert all(0 <= r.offset <= 65536 - r.length for r in a)


def test_zipf_popularity_matches_the_azure_trace():
    """The top 18.6 % of functions take 99.6 % of invocations in the
    Azure Functions trace: here the top 12 of 64 kernels."""
    reqs = schedule(SEED, seconds=60.0)
    counts = np.sort(np.bincount([r.kernel for r in reqs], minlength=64))
    assert counts[::-1][:12].sum() / counts.sum() == pytest.approx(
        0.996, abs=0.001)
    hot = [np.bincount([r.kernel for r in schedule(s)]).argmax()
           for s in (SEED, SEED + 1, 7)]
    assert len(set(hot)) == 1                  # the ranking is the mix's


def test_lengths_follow_the_execution_time_fit():
    spec = load("traffic", "steady")["length"]
    n = tr.lengths(spec, 4000, tr.rng_for(SEED, 0))
    # median of the fit, e ** -0.38 s, at a thousand elements a second
    assert np.median(n) == pytest.approx(1000 * np.exp(-0.38), rel=0.01)
    # clipped at both ends: P(t < 0.128 s) and P(t > 65.5 s) of the fit
    assert np.mean(n == 128) == pytest.approx(0.239, abs=0.002)
    assert np.mean(n == 65536) == pytest.approx(0.027, abs=0.002)


@pytest.mark.parametrize("weights,count,want", [
    ([1.0, 1.0, 1.0], 10, [4, 3, 3]),
    ([0.5, 0.3, 0.2], 7, [4, 2, 1]),
    ([1.0, 2.0 ** -2.84, 3.0 ** -2.84], 1, [1, 0, 0]),
])
def test_shares_split_by_largest_remainder(weights, count, want):
    got = tr.shares(np.array(weights), count)
    assert got.tolist() == want and got.sum() == count


@pytest.mark.parametrize("spec,n", [
    (load("configs", "faas64")["catalog"], 64),
    ({"file": "paper9.json", "random": 0}, 9),
])
def test_every_catalog_kernel_encodes_in_one_pass(spec, n):
    specs = catalog.build(spec, (-2.0, 2.0))
    assert len(specs) == n
    assert len({s.structure() for s in specs}) == len(specs)
    for s in specs:
        k = catalog.to_program(s)
        assert len(k.program.images) <= 16, s.name
        assert len(k.program.images) == s.depth


def test_reference_control_is_far_from_float32():
    cfg = load("configs", "faas64")
    specs = catalog.build(cfg["catalog"], cfg["input_range"])
    rng = np.random.default_rng(0)
    for s in specs[:12]:
        xs = [rng.uniform(-2, 2, 4096).astype(np.float32) for _ in s.inputs]
        want = reference.evaluate(s, xs)
        assert reference.gap(want, want) == 0.0
        low = reference.evaluate(s, xs, reference.bfloat16())
        assert reference.gap(low, want) > 1e-3, s.name
    assert reference.gap(None, want) == np.inf
    assert reference.gap([want[0][:-1]], want) == np.inf


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "faas64.steady", "--seed", str(SEED), "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ---------------------------------------------------------- trace reduction
def ev(plane, line, name, start, dur):
    return tracefile.Event(plane, line, name, float(start), float(dur))


def test_reduce_on_a_made_up_trace():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        ev(host, "main", "bench.window", 1000, 10000),
        ev(host, "main", "bench.submit", 3000, 1500),
        ev(host, "pump", "bench.pump_once", 2500, 4000),
        ev(dev, "XLA Ops", "fusion", 500, 1000),          # clipped to 500
        ev(dev, "XLA Ops", "fusion", 2000, 1000),
        ev(dev, "XLA Ops", "copy", 2500, 1000),           # overlaps: union
        ev(dev, "XLA Ops", "fusion", 9000, 4000),         # clipped to 2000
        ev(dev, "XLA Modules", "jit_vm_exec_multi_donated(7)", 2000, 1500),
    ]
    s = tracefile.reduce(events)
    assert s["window_s"] == pytest.approx(1e-5)
    assert s["busy_s"] == pytest.approx((500 + 1500 + 2000) / 1e9)
    assert s["op_seconds"]["fusion"] == pytest.approx(3500 / 1e9)
    assert s["module_seconds"] == {
        "jit_vm_exec_multi_donated(7)": pytest.approx(1.5e-6)}
    # each gap is named by the spans open at its midpoint
    assert s["gaps"][0] == ["bench.pump_once", pytest.approx(5500 / 1e9)]
    assert s["gaps"][1] == ["none", pytest.approx(500 / 1e9)]
    assert tracefile.top_ops(s)[0][0] == "fusion"
    events.append(ev(host, "main", "bench.claim", 1600, 300))
    events.append(ev(host, "pump", "bench.pump_once", 1700, 200))
    assert tracefile.reduce(events)["gaps"][1][0] == \
        "bench.claim+bench.pump_once"


class _Run:
    def __init__(self, summary, specs, delivered):
        self.trace, self.specs, self._d = summary, specs, delivered
        self.device = {"kind": "TPU v5 lite"}

    def delivered_in_trace(self):
        return self._d

    def peak(self, key):
        table = json.loads((BENCH / "peaks.json").read_text())
        return table["devices"][self.device["kind"]][key]


def test_reduce_on_a_trace_recorded_on_the_chip():
    """The events ``load`` kept from a faas64.steady trace of a 1-s window
    on one v5e (the window's span ran 2.03 s: the last submits waited)."""
    path = BENCH / "tests" / "data" / "steady_v5e_1s.json.gz"
    s = tracefile.reduce(tracefile.load_events(str(path)))
    assert s["chips"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["window_s"] == pytest.approx(2.03, rel=0.01)
    executor = sum(t for name, t in s["module_seconds"].items()
                   if any(m in name
                          for m in executor_roofline.EXECUTOR_MODULES))
    assert 0 < executor <= s["busy_s"] * 1.0001
    assert 0 < len(s["gaps"]) <= 10
    assert all(g[1] > 0 and g[0] for g in s["gaps"])
    assert sorted((g[1] for g in s["gaps"]), reverse=True) == \
        [g[1] for g in s["gaps"]]
    assert len(tracefile.top_ops(s)) <= 10
    # the roofline: bytes the requests need at 819 GB/s over executor time
    specs = catalog.build({"file": "paper9.json", "random": 0}, (-2, 2))
    reqs = [tr.Request(kernel=8, tenant="t", length=65536, offset=0)]
    got = executor_roofline.read(_Run(s, specs, reqs))
    want = 100.0 * 6 * 4 * 65536 / 819e9 / executor
    assert got == pytest.approx(want)


def test_load_reads_the_benchmarks_spans_from_a_profile(tmp_path):
    """A profile taken on the CPU: the window's span and the benchmark's
    own spans are read; with no device plane there is no busy time, and
    the idle share reads nothing rather than 0."""
    import jax
    import jax.numpy as jnp
    from chipbench.metrics import device_idle_share
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.submit"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    events = tracefile.load(tracefile.find_xplane(str(tmp_path)))
    assert {e.name for e in events} == {"bench.window", "bench.submit"}
    s = tracefile.reduce(events)
    assert s["chips"] == 0 and s["busy_s"] == 0.0

    class R:
        trace = s
    assert device_idle_share.read(R) is None
    fixture = tmp_path / "events.json.gz"
    tracefile.save_events(str(fixture), events)
    assert tracefile.load_events(str(fixture)) == events


def test_load_keeps_the_programs_host_spans(tmp_path):
    """The engine's and the pump's host spans are kept beside the
    benchmark's own, so the idle gaps name the program's stages; other
    host annotations are not."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for name in ("pump.tick", "engine.collect", "other.span"):
            with jax.profiler.TraceAnnotation(name):
                jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    events = tracefile.load(tracefile.find_xplane(str(tmp_path)))
    assert {e.name for e in events} == {"bench.window", "pump.tick",
                                        "engine.collect"}


def test_reduce_averages_busy_over_the_chips():
    """Four device planes, as a fleet on four chips traces: ``busy_s`` is
    the mean of each chip's, which ``chip_busy_s`` gives by plane; a chip
    of the cell that ran no op in the window counts as idle."""
    host = "/host:CPU"
    events = [ev(host, "main", "bench.window", 0, 10000)]
    for n, busy in enumerate((1000, 2000, 4000, 5000)):
        events.append(ev(f"/device:TPU:{n}", "XLA Ops", "fusion", 0, busy))
    s = tracefile.reduce(events, chips=4)
    assert s["chips"] == 4
    assert s["chip_busy_s"] == {f"/device:TPU:{n}": pytest.approx(b / 1e9)
                                for n, b in enumerate((1000, 2000, 4000,
                                                       5000))}
    assert s["busy_s"] == pytest.approx(3000 / 1e9)
    three = tracefile.reduce(events[:-1], chips=4)
    assert three["chips"] == 3
    assert three["busy_s"] == pytest.approx(7000 / 4 / 1e9)


@pytest.mark.parametrize("name", [
    m["name"] for kind in ("end_to_end", "per_layer")
    for m in json.loads((ROOT / "BENCHMARK.json").read_text())[kind]])
def test_every_metric_has_a_reader(name):
    """``<name>.<split>`` is read by ``<name>.py``."""
    from chipbench import harness
    assert callable(harness.reader(BENCH, name))


@pytest.mark.parametrize("largest,held", [(4096, True), (4097, False)])
def test_knee_refuses_a_rate_whose_rounds_outgrew_the_warm_up(largest, held):
    from chipbench import knee
    row = {"rate_rps": 100.0, "due": 1000, "delivered_in_window": 1000,
           "backlog_at_close": 0, "undrained": 0,
           "p50_p95_ms_first_half": [5.0, 9.0],
           "p50_p95_ms_second_half": [5.5, 9.5],
           "largest_round_tiles": largest, "warmed_up_to": 4096}
    assert knee.held(row) is held
