"""A fleet cell added to the benchmark as files only, run on the CPU.

A checkout whose benchmark gained a configuration with ``replicas: 4``
and a traffic mix that drains replica 1 at a third of the window and
adds a replica back at two thirds, and nothing else.  The cell asks for
four chips; ``fleet_child.py`` runs it on four fake CPU devices, in a
process of its own so that the devices exist whatever this process
imported first.  The harness's look for a TPU is skipped; all else is a
run as on the chip.  Below, the harness's refusals of fleets and events
it cannot run, checked in this process.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness

BENCH = Path(harness.__file__).resolve().parent
CHILD = BENCH / "tests" / "fleet_child.py"

FLEET_CONFIG = {
    "name": "tinyfleet", "source": "a test's own deployment",
    "catalog": {"random": 3, "seed": 5, "max_stages": 16,
                "max_statements": 16},
    "tenants": 2, "bank_capacity": 2, "replicas": 4, "dtype": "float32",
    "input_range": [-2.0, 2.0], "block_len": 1024,
    "compare": {"max_gap": 1e-05, "undelivered": 0},
}
DRAIN_TRAFFIC = {
    "who": "a test", "arrival": "poisson", "rate_rps": 30.0,
    "length": {"dist": "lognormal", "mu": -0.38, "sigma": 2.36,
               "elements_per_s": 1000, "min": 128, "max": 1024},
    "popularity": {"dist": "zipf", "s": 1.0, "ranking_seed": 3},
    "warmup_s": 0.5, "warmup_tiles_up_to": 16,
    "events": [{"at": 1 / 3, "do": "drain", "replica": 1},
               {"at": 2 / 3, "do": "add"}],
}


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """The fleet cell of a checkout that gained it by two data files."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tinyfleet",
                             "source": "https://example.org",
                             "file": "chipbench/configs/tinyfleet.json",
                             "reduced": [], "why": "a test's own"})
    bench["workloads"].append({"name": "tinyfleet.drain",
                               "config": "tinyfleet",
                               "traffic": "tiny_drain", "chips": 4,
                               "why": "a test's own"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench/configs/tinyfleet.json").write_text(
        json.dumps(FLEET_CONFIG))
    (root / "chipbench/traffic/tiny_drain.json").write_text(
        json.dumps(DRAIN_TRAFFIC))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(CHILD), str(root),
                        "tinyfleet.drain"], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_fleet_cell_added_as_files_is_correct(fleet):
    assert fleet["compared"]["max_gap"]["value"] == 0.0
    assert fleet["compared"]["undelivered"]["value"] == 0
    assert harness.verdict(fleet["compared"])
    assert fleet["failed"] == 0 and fleet["attempted"] == 90


def test_fleet_events_fire_on_time_in_order(fleet):
    drain, add = fleet["events"]
    assert (drain["do"], drain["replica"], add["do"]) == ("drain", 1, "add")
    for e in (drain, add):
        assert e["due_s"] == pytest.approx(e["at"] * fleet["seconds"])
        assert 0.0 <= e["fired_s"] - e["due_s"] < harness.POLL_S
        assert e["lock_wait_s"] >= 0.0 and e["held_s"] > 0.0
    assert drain["fired_s"] < add["fired_s"]
    # the added replica lands on the chip the drain left empty
    assert add["result"]["device"] == drain["result"]["device"]


def test_fleet_scales_down_and_up_once(fleet):
    d = fleet["deltas"]
    assert (d["scale_downs"], d["scale_ups"], d["replicas_retired"]) == \
        (1, 1, 1)
    assert d["requests"] == fleet["attempted"]


def test_fleet_rounds_run_on_their_replicas_devices(fleet):
    placed = fleet["placed"]
    assert all(got == [own] for own, got in placed)
    assert {own for own, _ in placed} == set(fleet["devices"])


def test_fleet_window_compiles_nothing(fleet):
    assert fleet["window_compiles"] == 0


def test_fleet_bank_loads_and_hits_keep_a_drained_replicas(fleet):
    """Loads and hits of ``Rig.stats`` add up to the kernel groups the
    rounds looked up plus the contexts the router loaded outside rounds,
    before and after a drain: the drained bank's stay counted."""
    g = fleet["groups"]
    for reading in (g["before"], g["after_drain"], g["after"]):
        assert reading["loads_plus_hits"] == \
            reading["groups"] + reading["prefetched"]
    assert g["after_drain"]["drained"] > 0
    assert g["after"]["groups"] > g["before"]["groups"]


# ------------------------------------------------------------- refusals
TINY = dict(FLEET_CONFIG)
del TINY["replicas"]
STEADY = {k: v for k, v in DRAIN_TRAFFIC.items() if k != "events"}


def rig_of(config, traffic, chips):
    cell = harness.Cell(root=BENCH.parent, name="refused", chips=chips,
                        config=config, traffic=traffic, metrics=[],
                        layer_metrics=[])
    return harness.Rig(cell, 7, require_tpu=False, log=lambda m: None,
                       use_cache=False)


def with_events(*events):
    return dict(STEADY, events=list(events))


@pytest.mark.parametrize("config,traffic,chips,says", [
    (dict(TINY, replicas=4), STEADY, 1, "one replica on each"),
    (dict(TINY, replicas=2), STEADY, 4, "one replica on each"),
    (dict(TINY, replicas="4"), STEADY, 4, "one replica on each"),
    (TINY, with_events({"at": 0.5, "do": "drain", "replica": 0}), 1,
     "one engine"),
    (dict(TINY, replicas=4), with_events({"at": 0.5, "do": "restart"}), 4,
     "'do' is one of"),
    (dict(TINY, replicas=4), with_events({"at": 1.5, "do": "add"}), 4,
     "0..1"),
    (dict(TINY, replicas=4), with_events({"at": -0.1, "do": "add"}), 4,
     "0..1"),
    (dict(TINY, replicas=4), with_events({"at": "1/3", "do": "add"}), 4,
     "0..1"),
    (dict(TINY, replicas=4), with_events({"at": 0.5, "do": "drain"}), 4,
     "has the keys"),
    (dict(TINY, replicas=4),
     with_events({"at": 0.5, "do": "add", "replica": 1}), 4, "has the keys"),
    (dict(TINY, replicas=4),
     with_events({"at": 0.5, "do": "drain", "replica": 4}), 4,
     "replicas 0..3"),
    (dict(TINY, replicas=4),
     with_events({"at": 0.2, "do": "drain", "replica": 3},
                 {"at": 0.4, "do": "drain", "replica": 3}), 4,
     "replicas 0..2"),
    (dict(TINY, replicas=1),
     with_events({"at": 0.5, "do": "drain", "replica": 0}), 1,
     "the last replica"),
])
def test_the_harness_refuses_a_fleet_or_event_it_cannot_run(
        config, traffic, chips, says):
    with pytest.raises(harness.BenchError, match=says):
        rig_of(config, traffic, chips)


def test_fewer_distinct_devices_than_replicas_is_refused():
    import jax
    with pytest.raises(harness.BenchError, match="4 replicas placed on 1"):
        harness.make_engine(dict(TINY, replicas=4), 4, jax.devices()[:1])


def test_a_configuration_without_replicas_builds_one_overlay_server():
    from repro.launch.serve import OverlayServer
    rig = rig_of(TINY, STEADY, 1)
    try:
        assert type(rig.server) is OverlayServer
        assert not rig.fleet and rig.event_plan == []
        assert set(harness.BANK_COUNTERS) <= set(rig.stats())
    finally:
        rig.close()
