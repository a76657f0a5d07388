"""Whole runs of the benchmark's harness on the CPU, at a tiny size.

The harness's look for a TPU is skipped (``require_tpu=False``); all else
is a run as on the chip: set-up, warm-up, an open-loop window through
``OverlayServer.submit`` and the pump, the drain, and the check against
the plain reference.  The cell is one that a test adds as files only (a
configuration, a traffic mix and a per-layer metric), which the harness
finds by name.
"""

import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import catalog, harness, reference
from chipbench.metrics import executor_roofline

BENCH = Path(harness.__file__).resolve().parent

TINY_CONFIG = {
    "name": "tiny", "source": "a test's own deployment",
    "catalog": {"random": 3, "seed": 5, "max_stages": 16,
                "max_statements": 16},
    "tenants": 2, "bank_capacity": 2, "dtype": "float32",
    "input_range": [-2.0, 2.0], "block_len": 1024,
    "compare": {"max_gap": 1e-05, "undelivered": 0},
}
TINY_TRAFFIC = {
    "who": "a test", "arrival": "poisson", "rate_rps": 30.0,
    "length": {"dist": "lognormal", "mu": -0.38, "sigma": 2.36,
               "elements_per_s": 1000, "min": 128, "max": 1024},
    "popularity": {"dist": "zipf", "s": 1.0, "ranking_seed": 3},
    "warmup_s": 0.5, "warmup_tiles_up_to": 16,
}
NEW_METRIC = '''"""rounds_in_window: rounds launched over the window (a test's own)."""


def read(run):
    return run.stat_delta("rounds")
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose benchmark gained a configuration, a traffic mix
    and a per-layer metric by files alone."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "https://example.org",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "a test's own"})
    bench["workloads"].append({"name": "tiny.open", "config": "tiny",
                               "traffic": "tiny_open", "chips": 1,
                               "why": "a test's own"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny.open")
    bench["per_layer"].append({
        "name": "rounds_in_window", "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "round formation",
        "moves": "latency_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (root / "chipbench/traffic/tiny_open.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (root / "chipbench/metrics/rounds_in_window.py").write_text(NEW_METRIC)
    return root


def tiny_run(root, seed=2 ** 33 + 7):
    cell = harness.load_cell("tiny.open", root=root)
    return harness.run_cell(cell, seed, 1.0, False, time.perf_counter(),
                            require_tpu=False, log=lambda m: None,
                            use_cache=False)


@pytest.fixture(scope="module")
def sound(root):
    return tiny_run(root)


def test_added_files_are_found_by_name(root, sound):
    cell = sound.cell
    assert cell.config["name"] == "tiny"
    assert cell.traffic["rate_rps"] == 30.0
    e2e = ["latency_p50_ms", "latency_p95_ms", "setup_s"]
    assert [m["name"] for m in cell.metrics] == e2e
    assert [m["name"] for m in cell.layer_metrics] == ["rounds_in_window"]
    got = harness.read_metrics(sound, cell.layer_metrics)
    assert got["rounds_in_window"]["value"] > 0
    assert set(harness.read_metrics(sound, cell.metrics)) == set(e2e)


def test_sound_run_is_correct(sound):
    compared, failed = harness.check_numbers(sound)
    assert harness.verdict(compared), compared
    assert failed == 0
    assert len(sound.reqs) == 30
    assert compared["max_gap"]["value"] <= 1e-05
    # the 2-slot bank over 3 kernels evicted during the run
    assert sound.stats1["evictions"] > 0


def test_control_in_the_programs_place_is_not_correct(sound):
    harness.put_control(sound)
    compared, failed = harness.check_numbers(sound)
    assert not harness.verdict(compared)
    assert compared["max_gap"]["value"] > 1e-05
    assert failed > 0


def _alter_one_answer(monkeypatch):
    """One element of each round's first answer altered in ``collect``,
    where the answers are produced."""
    from repro.core.overlay import Overlay
    collect = Overlay.collect

    def altered(self, plan, ys, host=False):
        outs = collect(self, plan, ys, host=host)
        first = np.array(outs[0][0], copy=True)
        first[0] += 1.0
        outs[0] = [first] + list(outs[0][1:])
        return outs

    monkeypatch.setattr(Overlay, "collect", altered)


def _skip_the_executor(monkeypatch):
    """Every round's result stack left as zeros: the device work skipped."""
    import jax.numpy as jnp
    from repro.core.overlay import Overlay
    execute = Overlay.execute

    def skipped(self, bank, batch):
        ys = execute(self, bank, batch)
        return None if ys is None else jnp.zeros_like(ys)

    monkeypatch.setattr(Overlay, "execute", skipped)


@pytest.mark.parametrize("fault", [_alter_one_answer, _skip_the_executor])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    compared, failed = harness.check_numbers(tiny_run(root, seed=11))
    assert not harness.verdict(compared)
    assert compared["max_gap"]["value"] > 1e-05
    assert failed > 0


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_reference_and_roofline_bytes_agree_across_executors(backend):
    """The reference agrees with each executor, and the roofline's byte
    count of the same requests does not depend on which served them."""
    from repro.launch.serve import OverlayServer
    specs = catalog.build(TINY_CONFIG["catalog"], (-2.0, 2.0))
    kernels = [catalog.to_program(s) for s in specs]
    rng = np.random.default_rng(3)
    reqs = [(k, rng.uniform(-2, 2, (len(specs[k].inputs), n))
             .astype(np.float32)) for k, n in
            [(0, 128), (1, 300), (2, 77), (0, 256), (2, 129)]]
    srv = OverlayServer(bank_capacity=2, backend=backend)
    tickets = {srv.submit(kernels[k], list(x)): i
               for i, (k, x) in enumerate(reqs)}
    got = srv.flush()
    for t, i in tickets.items():
        k, x = reqs[i]
        assert reference.gap(got[t], reference.evaluate(specs[k], list(x))) \
            == 0.0
    nbytes = executor_roofline.request_bytes(
        specs, [(reqs[i][0], len(got[t][0])) for t, i in tickets.items()])
    assert nbytes == sum((len(specs[k].inputs) + 1) * 4 * x.shape[1]
                         for k, x in reqs)


def test_a_traced_run_reads_the_windows_first_seconds(root, tmp_path):
    """The profiler traces the window; the reduction reads its first
    ``trace_s`` seconds, and the roofline counts the requests claimed in
    them."""
    from chipbench import tracefile
    cell = harness.load_cell("tiny.open", root=root)
    run = harness.run_cell(cell, 19, 1.0, True, time.perf_counter(),
                           require_tpu=False, log=lambda m: None,
                           trace_dir=str(tmp_path), use_cache=False,
                           trace_s=0.4)
    events = tracefile.load(tracefile.find_xplane(str(tmp_path)))
    assert 1.0 <= tracefile.reduce(events)["window_s"] < 1.5
    assert tracefile.reduce(events, max_s=0.4)["window_s"] == \
        pytest.approx(0.4)
    assert run.trace_end == pytest.approx(run.start + 0.4)
    traced = run.delivered_in_trace()
    assert 0 < len(traced) < len(run.reqs)
    compared, failed = harness.check_numbers(run)
    assert harness.verdict(compared) and failed == 0
