"""Zero-copy round pipeline tests (PR 9).

Three layers of defence around host-buffer reuse — precisely the kind of
optimisation that silently corrupts a delivered-but-unclaimed result:

* RoundArena unit behaviour: bucketed recycling, dirty-row scrubbing,
  free-list caps, leak-visible counters.
* Bit parity: the single-pass scatter ``assemble`` and the live-rows
  ``collect`` must reproduce the seed's ``assemble_reference`` /
  ``collect_reference`` buffers EXACTLY, with and without recycled
  (previously dirtied) blocks, donation on and off, both backends.
* Aliasing safety under the engines: results delivered from round N stay
  bit-stable (deep-compared snapshots) while rounds N+1..N+k reuse the
  arena — across all three round policies, pipelined flush, and the
  autoscale grow/drain path.
"""

import numpy as np
import pytest

from repro.core.arena import RoundArena
from repro.core.overlay import Overlay, compile_program
from repro.core.paper_bench import BENCH_NAMES, benchmark
from repro.launch.serve import OverlayServer, ShardedOverlayServer

ALL_NAMES = BENCH_NAMES + ("gradient",)


@pytest.fixture(scope="module")
def kernels():
    return {n: compile_program(benchmark(n)) for n in ALL_NAMES}


def _xs(kernel, batch, seed):
    rng = np.random.RandomState(seed)
    return [rng.uniform(-2, 2, (batch,)).astype(np.float32)
            for _ in kernel.dfg.inputs]


def _requests(kernels, n, seed, batch_pool=(17, 48, 64, 96, 200)):
    rng = np.random.RandomState(seed)
    names = list(kernels)
    out = []
    for i in range(n):
        k = kernels[names[i % len(names)]]
        out.append((k, _xs(k, int(rng.choice(batch_pool)), seed * 997 + i)))
    return out


# ============================================================ arena units
def test_checkout_recycle_reuses_block():
    a = RoundArena()
    b1 = a.checkout(8, 128, np.float32)
    assert b1.x.shape == (8, 32, 128) and b1.ids.shape == (8,)
    a.recycle(b1)
    b2 = a.checkout(8, 128, np.float32)
    assert b2 is b1                      # same pooled block, no realloc
    s = a.stats()
    assert s["allocations"] == 1 and s["checkouts"] == 2
    assert s["recycles"] == 1 and s["outstanding"] == 1


def test_distinct_buckets_do_not_share():
    a = RoundArena()
    b1 = a.checkout(8, 128, np.float32)
    a.recycle(b1)
    assert a.checkout(16, 128, np.float32) is not b1
    assert a.checkout(8, 256, np.float32) is not b1
    assert a.checkout(8, 128, np.float64) is not b1
    assert a.stats()["allocations"] == 4


def test_recycled_block_is_scrubbed_to_zeros():
    a = RoundArena()
    b = a.checkout(4, 128, np.float32)
    b.x[:, :5, :] = 7.0                  # a round dirties rows [0, 5)
    b.dirty_rows = 5
    b.ids[:] = 3
    a.recycle(b)
    b2 = a.checkout(4, 128, np.float32)
    assert b2 is b
    assert not b2.x.any()                # bit-identical to fresh zeros
    assert b2.dirty_rows == 0            # ids need no scrub: assemble
    # fully overwrites them every round


def test_scrub_honors_high_water_mark_only():
    a = RoundArena()
    b = a.checkout(4, 128, np.float32)
    b.dirty_rows = 2
    # simulate an out-of-contract write ABOVE the declared mark: scrub
    # must not be expected to clean it (documents the invariant)
    b.x[:, 3, :] = 9.0
    a.recycle(b)
    b2 = a.checkout(4, 128, np.float32)
    assert b2.x[:, 3, :].any()           # row 3 was never declared dirty


def test_free_list_cap_discards_excess():
    a = RoundArena(max_free_per_bucket=1)
    b1 = a.checkout(4, 128, np.float32)
    b2 = a.checkout(4, 128, np.float32)
    a.recycle(b1)
    a.recycle(b2)
    s = a.stats()
    assert s["recycles"] == 1 and s["discards"] == 1
    assert s["free_blocks"] == 1 and s["outstanding"] == 0


def test_recycle_none_is_noop():
    a = RoundArena()
    a.recycle(None)
    assert a.stats()["outstanding"] == 0


def test_outstanding_counts_leaks():
    a = RoundArena()
    a.checkout(4, 128, np.float32)
    a.checkout(4, 128, np.float32)
    s = a.stats()
    assert s["outstanding"] == 2 and s["peak_outstanding"] == 2
    assert s["pooled_bytes"] == 0


# ====================================================== bitwise stage parity
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("donate", [False, True])
def test_arena_pipeline_bitwise_matches_reference(kernels, backend, donate):
    ov = Overlay(backend=backend, arena=RoundArena(), donate=donate)
    ref = Overlay(backend=backend)
    bank = ov.load_many(kernels.values(), capacity=len(kernels))
    for seed in range(3):                # round 2+ exercises recycled blocks
        reqs = _requests(kernels, 10, seed=seed)
        p = ov.plan(bank, reqs, pin=True)
        batch = ov.assemble(p)
        p_ref = ref.plan(bank, reqs)
        batch_ref = ref.assemble_reference(p_ref)
        np.testing.assert_array_equal(np.asarray(batch[0]),
                                      np.asarray(batch_ref[0]))
        np.testing.assert_array_equal(np.asarray(batch[1]),
                                      np.asarray(batch_ref[1]))
        ys = ov.execute(bank, batch)
        ys_ref = ref.execute(bank, batch_ref)
        got = ov.collect(p, ys, host=True)
        want = ref.collect_reference(p_ref, ys_ref, host=True)
        lazy = ref.collect_reference(p_ref, ys_ref, host=False)
        for g, w, l in zip(got, want, lazy):
            for a, b, c in zip(g, w, l):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        p.release(bank)
    s = ov.arena.stats()
    assert s["outstanding"] == 0 and s["recycles"] >= 2


def test_dispatch_recycles_its_block(kernels):
    ov = Overlay(arena=RoundArena())
    bank = ov.load_many(kernels.values(), capacity=len(kernels))
    ref = Overlay()
    for seed in range(2):
        reqs = _requests(kernels, 6, seed=seed)
        got = ov.dispatch(bank, reqs)
        want = ref.dispatch(bank, reqs)
        for g, w in zip(got, want):
            for a, b in zip(g, w):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s = ov.arena.stats()
    assert s["outstanding"] == 0         # the sync oracle must not leak


def test_empty_round_skips_arena(kernels):
    ov = Overlay(arena=RoundArena())
    bank = ov.load_many(kernels.values(), capacity=len(kernels))
    k = kernels["poly5"]
    p = ov.plan(bank, [(k, [np.zeros(0, np.float32)
                            for _ in k.dfg.inputs])])
    assert ov.assemble(p) is None
    assert ov.arena.stats()["checkouts"] == 0
    outs = ov.collect(p, None)
    assert outs[0][0].shape == (0,)


def test_assemble_reassembly_does_not_leak(kernels):
    ov = Overlay(arena=RoundArena())
    bank = ov.load_many(kernels.values(), capacity=len(kernels))
    p = ov.plan(bank, _requests(kernels, 4, seed=0))
    ov.assemble(p)
    ov.assemble(p)                       # re-assembled plan recycles first
    assert ov.arena.stats()["outstanding"] == 1
    p.release(bank)
    assert ov.arena.stats()["outstanding"] == 0


# =========================================================== device routing
def test_assemble_places_on_device_execute_skips_put(kernels, monkeypatch):
    """The redundant per-round ``device_put`` in execute is gone: a batch
    assembled by a device-pinned overlay is already resident."""
    import jax

    from repro.core import overlay as overlay_mod
    dev = jax.devices()[0]
    ov = Overlay(device=dev, arena=RoundArena())
    bank = ov.load_many(kernels.values(), capacity=len(kernels))
    p = ov.plan(bank, _requests(kernels, 4, seed=1))
    batch = ov.assemble(p)
    assert batch[0].sharding.device_set == {dev}
    assert batch[1].sharding.device_set == {dev}
    calls = []
    orig = jax.device_put
    monkeypatch.setattr(overlay_mod.jax, "device_put",
                        lambda *a, **kw: calls.append(a) or orig(*a, **kw))
    ys = ov.execute(bank, batch)
    assert calls == []                   # no placement on the hot path
    assert ys is not None
    p.release(bank)


def test_execute_still_places_foreign_batches(kernels):
    """A batch built off-device (e.g. by a plain overlay) must still be
    co-located with the bank — the skip is residency-aware, not blind."""
    import jax
    dev = jax.devices()[0]
    plain = Overlay()                    # no device pin: default placement
    ov = Overlay(device=dev)
    bank = ov.load_many(kernels.values(), capacity=len(kernels))
    p = plain.plan(bank, _requests(kernels, 4, seed=2))
    batch = plain.assemble_reference(p)
    ys = ov.execute(bank, batch)         # must not raise a placement error
    assert np.asarray(ys).shape[0] == p.g_pad


# ======================================================== engine integration
def test_engine_stats_expose_arena_and_stage_walls(kernels):
    srv = OverlayServer(bank_capacity=8)
    for i in range(6):
        k = kernels[list(kernels)[i % len(kernels)]]
        srv.submit(k, _xs(k, 64, i))
    srv.flush()
    s = srv.stats()
    assert s["arena"] is not None
    assert s["arena"]["checkouts"] > 0
    assert s["arena"]["outstanding"] == 0          # all rounds retired
    walls = s["stage_walls"]
    assert set(walls) == {"plan_s", "assemble_s", "launch_s",
                          "device_wait_s", "collect_s"}
    assert walls["assemble_s"] > 0 and walls["collect_s"] > 0


def test_unattached_bank_reports_arena_none():
    from repro.core.bank import ContextBank
    assert ContextBank(2).stats()["arena"] is None


# ================================================= aliasing-safety property
@pytest.mark.parametrize("policy", ["drr", "coalesce", "dynamic"])
def test_round_n_results_bitstable_while_arena_reused(kernels, policy):
    """Results delivered from round N are deep-snapshot-stable while
    rounds N+1..N+k check the same arena blocks back out — across all
    three round policies, with the pipelined flush path live."""
    srv = OverlayServer(bank_capacity=8, round_policy=policy,
                        max_inflight=2, round_kernels=4)
    oracle = OverlayServer(bank_capacity=16)
    names = list(kernels)
    rng = np.random.RandomState(42)
    snapshots = {}
    live = {}
    for wave in range(5):
        pairs = []
        for i in range(8):
            k = kernels[names[int(rng.randint(len(names)))]]
            xs = _xs(k, int(rng.choice((48, 96, 130))), wave * 100 + i)
            pairs.append((srv.submit(k, xs), oracle.submit(k, xs)))
        got, want = srv.flush(), oracle.flush_sync()
        for gt, ot in pairs:
            ys = got[gt]
            live[gt] = ys                          # keep the views alive
            snapshots[gt] = ([np.array(y, copy=True) for y in ys],
                             [np.asarray(w) for w in want[ot]])
        # every PREVIOUS wave's delivered views must still hold the
        # bytes they held at delivery (and the oracle's bytes)
        for t, (snap, orc) in snapshots.items():
            for y, s, w in zip(live[t], snap, orc):
                np.testing.assert_array_equal(np.asarray(y), s)
                np.testing.assert_array_equal(np.asarray(y), w)
    assert srv.stats()["arena"]["recycles"] > 0    # reuse actually happened
    assert srv.stats()["arena"]["outstanding"] == 0


def test_results_bitstable_across_autoscale_grow_drain(kernels):
    """The grow/drain path must not disturb delivered bytes either: the
    drained replica's in-flight rounds retire through the same
    release/recycle protocol, and new replicas get their own arenas."""
    srv = ShardedOverlayServer(n_replicas=1, bank_capacity=6,
                               round_kernels=3, max_inflight=2)
    oracle = OverlayServer(bank_capacity=16)
    names = list(kernels)
    rng = np.random.RandomState(7)

    def submit_wave(n, seed):
        pairs = []
        for i in range(n):
            k = kernels[names[i % len(names)]]
            xs = _xs(k, int(rng.choice((48, 64, 96))), seed * 1000 + i)
            pairs.append((srv.submit(k, xs), oracle.submit(k, xs)))
        return pairs

    snapshots = {}
    live = {}

    def deliver_and_check(pairs):
        got, want = srv.flush(), oracle.flush_sync()
        for gt, ot in pairs:
            live[gt] = got[gt]
            snapshots[gt] = ([np.array(y, copy=True) for y in got[gt]],
                             [np.asarray(w) for w in want[ot]])
        for t, (snap, orc) in snapshots.items():
            for y, s, w in zip(live[t], snap, orc):
                np.testing.assert_array_equal(np.asarray(y), s)
                np.testing.assert_array_equal(np.asarray(y), w)

    deliver_and_check(submit_wave(10, seed=1))
    srv.add_replica()                              # grow under live results
    deliver_and_check(submit_wave(12, seed=2))
    # launch rounds so the drain path walks in-flight retirement
    pairs = submit_wave(12, seed=3)
    for rep in srv.replicas:
        rep._fill_pipeline()
    srv.drain_replica(0)                           # drain under live results
    deliver_and_check(pairs)
    for bank in srv.banks:
        arena = bank.stats()["arena"]
        assert arena is not None and arena["outstanding"] == 0
