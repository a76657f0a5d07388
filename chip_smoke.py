"""Serve the overlay on a TPU and check every result: the chip smoke test.

Drives the multi-tenant engine (``repro.launch.serve.OverlayServer``) on
one chip with both executors, the jnp VM and the Pallas TMFU kernel
compiled by Mosaic, over a seeded backlog of the size a kernel-serving
deployment holds: 2,000 requests from 6 tenants over the 9 paper kernels
(more than the bank of 8 holds, so LRU eviction runs under load), with
request lengths log-uniform from 128 to 65,536 elements.  Every result is
compared bit for bit with the DFG oracle (``dfg_eval``) on the same
inputs; a subset is also served through the ``flush_sync`` barrier
oracle, and back-to-back ``Overlay.dispatch`` calls check that no round
reads a recycled staging block.  Then the CLI entry point runs in-process.

    python chip_smoke.py                # one chip: both executors + CLI
    python chip_smoke.py --four-chips   # only the 4-replica fleet (4 chips)

It exits non-zero, and prints no result line, when JAX finds no TPU or a
check fails.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_persistent_cache  # noqa: E402
from repro.core import overlay as overlay_mod  # noqa: E402
from repro.core import vm  # noqa: E402
from repro.core.overlay import compile_program  # noqa: E402
from repro.core.paper_bench import BENCH_NAMES, benchmark  # noqa: E402
from repro.kernels.tmfu import kernel as tmfu_kernel  # noqa: E402
from repro.kernels.tmfu.ops import _imm_to_i32  # noqa: E402
from repro.launch import serve  # noqa: E402

KERNEL_NAMES = BENCH_NAMES + ("gradient",)
#: the backlog: requests from tenants, lengths log-uniform in [MIN_LEN,
#: MAX_LEN], submitted WAVE at a time with a drain after each wave; the
#: first SYNC_WAVES waves are served again through ``flush_sync``, whose
#: lazy per-request slices compile one small executable each
N_REQUESTS, N_TENANTS, MIN_LEN, MAX_LEN, WAVE = 2000, 6, 128, 65536, 40
SYNC_WAVES = 4
#: what ``stats()["executor"]`` must report for each backend on the chip
EXECUTORS = {"jnp": "jnp", "pallas": "pallas-mosaic"}
#: the jitted executables of the served path; none may grow after warmup
EXECUTABLES = {
    "vm_exec_multi": vm.vm_exec_multi,
    "vm_exec_multi_donated": vm.vm_exec_multi_donated,
    "tmfu_pipeline_rf_multi": tmfu_kernel.tmfu_pipeline_rf_multi,
    "tmfu_pipeline_rf_multi_donated":
        tmfu_kernel.tmfu_pipeline_rf_multi_donated,
    "_gather_live": overlay_mod._gather_live,
}


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Counts XLA backend compiles and their seconds, phase by phase."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def mark(self):
        return self.n, self.seconds

    def since(self, mark) -> str:
        return f"{self.n - mark[0]} compiles, {self.seconds - mark[1]:.2f} s"


def cache_sizes() -> dict:
    return {name: fn._cache_size() for name, fn in EXECUTABLES.items()}


# ---------------------------------------------------------------- workload
class Workload:
    """Seeded requests, their waves, and the oracle's answer to each.

    Inputs are drawn in bulk, one [n_inputs, total] block per kernel;
    request ``i`` reads a slice of its kernel's block.  The oracle runs
    ``dfg_eval`` once per kernel over the whole block (every op is
    elementwise, so a slice of the answer is the answer for the slice).
    """

    def __init__(self, n_requests, n_tenants, min_len, max_len, wave, seed):
        rng = np.random.default_rng(seed)
        self.kernels = {n: compile_program(benchmark(n))
                        for n in KERNEL_NAMES}
        # skewed popularity: the k-th kernel of a seeded order is drawn
        # with weight 1/k, so some kernels stay hot and the tail churns
        order = rng.permutation(len(KERNEL_NAMES))
        weight = 1.0 / (1 + np.argsort(order))
        pick = rng.choice(len(KERNEL_NAMES), n_requests,
                          p=weight / weight.sum())
        lens = np.exp(rng.uniform(np.log(min_len), np.log(max_len),
                                  n_requests)).astype(np.int64)
        self.tenants = [f"tenant{t}" for t in
                        rng.integers(0, n_tenants, n_requests)]
        self.names = [KERNEL_NAMES[p] for p in pick]
        self.lens = lens
        self.xs = [None] * n_requests
        self.want = [None] * n_requests
        for name in KERNEL_NAMES:
            idx = [i for i in range(n_requests) if self.names[i] == name]
            if not idx:
                continue
            dfg = self.kernels[name].dfg
            total = int(lens[idx].sum())
            block = rng.uniform(-2, 2, (len(dfg.inputs), total)) \
                .astype(np.float32)
            ref = vm.dfg_eval(dfg, {n: jnp.asarray(block[j])
                                    for j, n in enumerate(dfg.inputs)})
            ref = [np.asarray(ref[o]) for o in dfg.outputs]
            off = 0
            for i in idx:
                n = int(lens[i])
                self.xs[i] = [block[j, off:off + n]
                              for j in range(len(dfg.inputs))]
                self.want[i] = [r[off:off + n] for r in ref]
                off += n
        self.waves = [list(range(s, min(s + wave, n_requests)))
                      for s in range(0, n_requests, wave)]

    def request(self, i):
        return self.kernels[self.names[i]], self.xs[i], self.tenants[i]

    @property
    def elements(self) -> int:
        return int(self.lens.sum())


def bits_equal(got, want) -> bool:
    got = np.asarray(got)
    return (got.shape == want.shape and got.dtype == want.dtype
            and np.array_equal(got.view(np.uint32), want.view(np.uint32)))


def check(wl: Workload, results: dict, what: str) -> None:
    """Every result equals the oracle bit for bit; names the first misses."""
    bad = [i for i, outs in results.items()
           if len(outs) != len(wl.want[i])
           or not all(bits_equal(g, w) for g, w in zip(outs, wl.want[i]))]
    if bad:
        worst = {}
        for i in bad:
            d = max(float(np.max(np.abs(np.asarray(g, np.float64) - w)))
                    for g, w in zip(results[i], wl.want[i]))
            worst[wl.names[i]] = max(worst.get(wl.names[i], 0.0), d)
        raise AssertionError(f"{what}: {len(bad)}/{len(results)} results "
                             f"differ from dfg_eval; max |diff| by kernel "
                             f"{worst}")


def serve_waves(srv, wl: Workload, waves) -> dict:
    """Submit wave by wave; drain even waves with the pipelined ``flush``
    and odd waves through ``as_completed``.  Returns request -> outputs."""
    results = {}
    for w, idxs in enumerate(waves):
        tickets = {}
        for i in idxs:
            k, xs, tenant = wl.request(i)
            tickets[srv.submit(k, xs, tenant=tenant)] = i
        got = srv.flush() if w % 2 == 0 else dict(srv.as_completed())
        if set(got) != set(tickets):
            raise AssertionError(f"wave {w}: delivered {len(got)} of "
                                 f"{len(tickets)} tickets")
        for t, outs in got.items():
            results[tickets[t]] = outs
    return results


def assert_mosaic_kernel(srv) -> None:
    """The compiled Pallas executable of this bank holds a Mosaic kernel."""
    op, src_a, src_b, imm = srv.bank.tree()
    x = jax.ShapeDtypeStruct((8, 32, srv.tile), jnp.float32)
    ids = jax.ShapeDtypeStruct((8,), jnp.int32)
    text = tmfu_kernel.tmfu_pipeline_rf_multi_donated.lower(
        op, src_a, src_b, _imm_to_i32(imm), ids, x,
        interpret=False).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("Pallas executable holds no tpu_custom_call")


def back_to_back_dispatch(srv, wl: Workload) -> int:
    """Three ``Overlay.dispatch`` rounds with no block between them.

    ``dispatch`` hands its arena block back right after the asynchronous
    launch, so the next round's ``assemble`` rewrites the same host block
    while the previous transfer may still be in flight: a round that read
    the recycled block would differ from the oracle.  The rounds take the
    24 longest requests, 8 to a round (so at most 8 kernels each).
    """
    longest = np.argsort(-wl.lens, kind="stable")[:24].tolist()
    rounds = [longest[r:r + 8] for r in range(0, len(longest), 8)]
    outs = [srv.overlay.dispatch(
        srv.bank, [(wl.kernels[wl.names[i]], wl.xs[i]) for i in r],
        tile=srv.tile) for r in rounds]
    jax.block_until_ready(outs)
    for r, o in zip(rounds, outs):
        check(wl, dict(zip(r, o)), "back-to-back dispatch")
    return len(longest)


# ------------------------------------------------------------------ phases
def serving_phase(wl: Workload, backend: str, clock: CompileClock,
                  sync_waves: int) -> None:
    """One backend: tile 128 warm + measured passes, the flush_sync and
    back-to-back checks, then one pass at tile 512."""
    t0, c0 = time.perf_counter(), clock.mark()
    srv = serve.OverlayServer(bank_capacity=8, tile=128, backend=backend)
    warm = serve_waves(srv, wl, wl.waves)
    check(wl, warm, f"{backend} tile 128 warmup")
    del warm
    log(f"[{backend}] tile 128 warmup: {len(wl.lens)} requests correct; "
        f"{clock.since(c0)}; {time.perf_counter() - t0:.1f} s")

    before, t1, c1 = cache_sizes(), time.perf_counter(), clock.mark()
    results = serve_waves(srv, wl, wl.waves)
    after = cache_sizes()
    grown = {n: after[n] - v for n, v in before.items() if after[n] != v}
    if grown or clock.n != c1[0]:
        raise AssertionError(f"[{backend}] compiled after warmup: "
                             f"{clock.since(c1)}, retraced {grown}")
    check(wl, results, f"{backend} tile 128")
    st = srv.stats()
    log(f"[{backend}] tile 128 measured pass: {len(results)} requests, "
        f"{wl.elements} elements, all equal to dfg_eval; retraces 0; "
        f"{clock.since(c1)}; {time.perf_counter() - t1:.1f} s; "
        f"rounds {st['rounds']} and evictions {st['evictions']} over both "
        f"passes, executor {st['executor']}")
    if st["executor"] != EXECUTORS[backend]:
        raise AssertionError(f"stats() executor {st['executor']!r}, "
                             f"expected {EXECUTORS[backend]!r}")
    if st["evictions"] == 0:
        raise AssertionError("the backlog never evicted a context")
    if backend == "pallas":
        assert_mosaic_kernel(srv)
        log("[pallas] compiled executable holds tpu_custom_call")

    c2 = clock.mark()
    sync_idx = [i for w in wl.waves[:sync_waves] for i in w]
    tickets = {}
    for i in sync_idx:
        k, xs, tenant = wl.request(i)
        tickets[srv.submit(k, xs, tenant=tenant)] = i
    synced = srv.flush_sync()
    mismatch = [tickets[t] for t, outs in synced.items()
                if not all(bits_equal(g, w) for g, w in
                           zip(outs, results[tickets[t]]))]
    if len(synced) != len(sync_idx) or mismatch:
        raise AssertionError(f"[{backend}] flush_sync differs from the "
                             f"pipelined drain on {len(mismatch)} requests")
    n_b2b = back_to_back_dispatch(srv, wl)
    log(f"[{backend}] flush_sync oracle: {len(sync_idx)} requests equal; "
        f"back-to-back dispatch: {n_b2b} requests equal; "
        f"{clock.since(c2)}")
    del results, synced

    t3, c3 = time.perf_counter(), clock.mark()
    srv512 = serve.OverlayServer(bank_capacity=8, tile=512, backend=backend)
    check(wl, serve_waves(srv512, wl, wl.waves), f"{backend} tile 512")
    st = srv512.stats()
    log(f"[{backend}] tile 512 pass: {len(wl.lens)} requests correct; "
        f"rounds {st['rounds']}, evictions {st['evictions']}; "
        f"{clock.since(c3)}; {time.perf_counter() - t3:.1f} s")
    log(f"[{backend}] phase total {time.perf_counter() - t0:.1f} s, "
        f"{clock.since(c0)}")


def cli_phase(clock: CompileClock) -> None:
    for backend in ("pallas", "jnp"):
        c0, t0 = clock.mark(), time.perf_counter()
        rc = serve.main(["--overlay-demo", "--backend", backend,
                         "--bank", "4", "--requests", "36",
                         "--req-batch", "4096"])
        if rc != 0:
            raise AssertionError(f"serve.main --backend {backend} -> {rc}")
        log(f"[cli] --overlay-demo --backend {backend}: rc 0; "
            f"{clock.since(c0)}; {time.perf_counter() - t0:.1f} s")


def four_chip_phase(wl: Workload, clock: CompileClock) -> None:
    """The 4-replica fleet: distinct devices, residency on each, one drain
    and one add mid-run, bit-equal to a single-bank engine."""
    devices = jax.devices()[:4]
    for backend in ("jnp", "pallas"):
        c0, t0 = clock.mark(), time.perf_counter()
        single = serve.OverlayServer(bank_capacity=8, tile=128,
                                     backend=backend, device=devices[0])
        oracle = serve_waves(single, wl, wl.waves)
        check(wl, oracle, f"{backend} single-bank oracle")

        fleet = serve.ShardedOverlayServer(n_replicas=4, bank_capacity=8,
                                           tile=128, backend=backend,
                                           devices=devices)
        drain_at, add_at = len(wl.waves) // 3, 2 * len(wl.waves) // 3
        results = {}
        for w, idxs in enumerate(wl.waves):
            if w == drain_at:
                fleet.drain_replica(1)
            if w == add_at:
                fleet.add_replica()
            assert_fleet_placement(fleet, drained=drain_at <= w < add_at)
            tickets = {}
            for i in idxs:
                k, xs, tenant = wl.request(i)
                tickets[fleet.submit(k, xs, tenant=tenant)] = i
            for rep in fleet.replicas:
                rep._fill_pipeline()
                for inf in rep._inflight:
                    if inf.ys is not None and inf.ys.devices() != {rep.device}:
                        raise AssertionError("a round ran off its replica's "
                                             "device")
            got = (fleet.flush() if w % 2 == 0
                   else dict(fleet.as_completed()))
            if set(got) != set(tickets):
                raise AssertionError(f"fleet wave {w}: delivered "
                                     f"{len(got)} of {len(tickets)}")
            for t, outs in got.items():
                results[tickets[t]] = outs
        diff = [i for i in results
                if not all(bits_equal(g, w) for g, w in
                           zip(results[i], oracle[i]))]
        if len(results) != len(wl.lens) or diff:
            raise AssertionError(f"[{backend}] fleet differs from the "
                                 f"single-bank engine on {len(diff)} "
                                 f"requests")
        st = fleet.stats()
        log(f"[four-chip {backend}] {len(results)} requests over 4 "
            f"replicas on devices {sorted(d.id for d in fleet.devices)}, "
            f"drain at wave {drain_at}, add at wave {add_at}: all equal to "
            f"the single-bank engine and dfg_eval; scale_downs "
            f"{st['scale_downs']}, scale_ups {st['scale_ups']}, evictions "
            f"{st['evictions']}, executors "
            f"{sorted({p['executor'] for p in st['per_replica']})}; "
            f"{clock.since(c0)}; {time.perf_counter() - t0:.1f} s")


def assert_fleet_placement(fleet, drained: bool) -> None:
    want = 3 if drained else 4
    ids = [d.id for d in fleet.devices]
    if len(ids) != want or len(set(ids)) != want:
        raise AssertionError(f"fleet placement {ids}: expected {want} "
                             f"distinct devices")
    for rep in fleet.replicas:
        for arr in rep.bank.tree() + (rep.bank.out_idx,):
            if arr.devices() != {rep.device}:
                raise AssertionError("a replica's bank is off its device")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the backlog's kernels, lengths and data")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica fleet phase (4 chips)")
    args = ap.parse_args(argv)

    use_persistent_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind}, {len(devices)} device(s), "
        f"jax {jax.__version__}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    clock = CompileClock()
    t0 = time.perf_counter()
    wl = Workload(N_REQUESTS, N_TENANTS, MIN_LEN, MAX_LEN, WAVE, args.seed)
    log(f"workload: {len(wl.lens)} requests, {wl.elements} elements, "
        f"{len(wl.waves)} waves, {len(KERNEL_NAMES)} kernels, "
        f"{N_TENANTS} tenants; oracle ready in "
        f"{time.perf_counter() - t0:.1f} s")
    if args.four_chips:
        four_chip_phase(wl, clock)
    else:
        for backend in ("jnp", "pallas"):
            serving_phase(wl, backend, clock, SYNC_WAVES)
        cli_phase(clock)
    log(f"total {time.perf_counter() - t0:.1f} s, "
        f"{clock.n} compiles ({clock.seconds:.1f} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
