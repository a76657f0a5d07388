"""A fleet cell of a files-only checkout, run on four fake CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python chipbench/tests/fleet_child.py <checkout> <workload>

``test_chipbench_fleet.py`` starts this in a process of its own, so that
the four devices exist whatever the test process imported first.  It
imports the benchmark from the checkout, runs the workload through
``harness.run_cell`` (the look for a TPU skipped), and prints one JSON
line: the compared numbers, the events as the run recorded them, the
window's counter deltas and compiles, and the devices each round's
result lay on beside its replica's.  Then, on a fleet of its own, it
counts the kernel groups the banks looked up before and after a drain
beside the bank loads and hits of ``Rig.stats``.
"""

import functools
import json
import sys
import time
from pathlib import Path

CHECKOUT, WORKLOAD = Path(sys.argv[1]), sys.argv[2]
sys.path[:0] = [str(CHECKOUT), str(Path(__file__).resolve().parents[2]
                                   / "src")]

import jax  # noqa: E402
from repro.core.bank import ContextBank  # noqa: E402
from repro.core.overlay import Overlay  # noqa: E402

from chipbench import harness  # noqa: E402

SEED = 2 ** 33 + 7
DELTAS = ("scale_ups", "scale_downs", "replicas_retired", "loads", "hits",
          "rounds", "requests")


def run_the_cell() -> dict:
    placed = []
    execute = Overlay.execute

    def recorded(self, bank, batch):
        ys = execute(self, bank, batch)
        if ys is not None:
            placed.append([str(self.device),
                           sorted(str(d) for d in ys.devices())])
        return ys

    Overlay.execute = recorded
    try:
        cell = harness.load_cell(WORKLOAD, root=CHECKOUT)
        run = harness.run_cell(cell, SEED, 3.0, False, time.perf_counter(),
                               require_tpu=False, use_cache=False,
                               log=lambda m: print(m, file=sys.stderr))
    finally:
        Overlay.execute = execute
    compared, failed = harness.check_numbers(run)
    return {"compared": compared, "failed": failed,
            "attempted": len(run.reqs), "seconds": run.seconds,
            "events": run.events,
            "deltas": {k: run.stats1[k] - run.stats0[k] for k in DELTAS},
            "window_compiles": run.window_compiles,
            "placed": placed, "devices": [str(d) for d in jax.devices()]}


def count_groups(cell) -> dict:
    """Kernel groups looked up by rounds (``ContextBank.acquire``) and
    contexts loaded outside rounds (``ContextBank.prefetch``: the
    router's, at a route miss) beside the bank loads and hits of
    ``Rig.stats``, before and after the replica that served most drains."""
    acquired, prefetched = [0], [0]
    acquire, prefetch = ContextBank.acquire, ContextBank.prefetch

    def counted(self, kernel, pin=False):
        acquired[0] += 1
        return acquire(self, kernel, pin=pin)

    def counted_loads(self, kernels):
        n = self.n_loads
        out = prefetch(self, kernels)
        prefetched[0] += self.n_loads - n
        return out

    ContextBank.acquire, ContextBank.prefetch = counted, counted_loads
    rig = harness.Rig(cell, SEED, require_tpu=False, use_cache=False,
                      log=lambda m: None)
    try:
        def serve(n):
            """``n`` requests submitted at once, so that the router
            spreads them over the replicas by their queues."""
            tickets = []
            for i in range(n):
                k = i % len(rig.gen.kernels)
                xs = [rig.blocks[k][j, :300] for j in range(
                    rig.blocks[k].shape[0])]
                tickets.append(rig.pump.submit(rig.gen.kernels[k], xs))
            for t in tickets:
                rig.pump.result(t, timeout=60)

        def reading():
            s = rig.stats()
            return {"loads_plus_hits": s["loads"] + s["hits"],
                    "groups": acquired[0], "prefetched": prefetched[0],
                    "drained": rig.retired["loads"] + rig.retired["hits"]}

        serve(12)
        before = reading()
        # the router places requests by the replicas' queues at the time,
        # so which banks loaded anything varies from run to run: drain
        # one that did
        reps = rig.stats()["per_replica"]
        busiest = max(range(len(reps)),
                      key=lambda i: reps[i]["loads"] + reps[i]["hits"])
        rig.served.call(functools.partial(rig._drain, i=busiest),
                        "bench.event")
        after_drain = reading()
        serve(12)
        after = reading()
    finally:
        rig.close()
        ContextBank.acquire, ContextBank.prefetch = acquire, prefetch
    return {"before": before, "after_drain": after_drain, "after": after}


if __name__ == "__main__":
    out = {"groups": count_groups(harness.load_cell(WORKLOAD, root=CHECKOUT))}
    out.update(run_the_cell())
    print(json.dumps(out, default=str), flush=True)
