"""Async streaming engine tests: ordering, parity, fairness, pinning.

The PR acceptance bar for the streaming OverlayServer:

* streamed results (``as_completed`` / ``result`` / pipelined ``flush``)
  are BIT-FOR-BIT identical to the synchronous ``Overlay.dispatch`` path;
* a hot tenant cannot starve a cold one — deficit-round-robin bounds the
  cold tenant's wait to O(1) rounds regardless of backlog;
* per-tenant token-bucket admission control rejects over-rate submits
  deterministically (injectable clock);
* contexts pinned by in-flight rounds survive LRU pressure, and the
  engine never leaks pins.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bank import BankError, ContextBank
from repro.core.overlay import Overlay, compile_program
from repro.core.paper_bench import BENCH_NAMES, benchmark
from repro.launch.serve import (AdmissionError, OverlayServer, TokenBucket)

ALL_NAMES = BENCH_NAMES + ("gradient",)


@pytest.fixture(scope="module")
def kernels():
    return {n: compile_program(benchmark(n)) for n in ALL_NAMES}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _xs(kernel, batch, seed):
    rng = np.random.RandomState(seed)
    return [rng.uniform(-2, 2, (batch,)).astype(np.float32)
            for _ in kernel.dfg.inputs]


def _dispatch_oracle(kernels_xs, bank_capacity=16):
    """The synchronous one-shot path: a fresh bank + Overlay.dispatch."""
    ov = Overlay()
    bank = ContextBank(bank_capacity)
    return ov.dispatch(bank, kernels_xs)


# ------------------------------------------------------------------- parity
def test_streamed_results_match_dispatch_bitexact(kernels):
    """as_completed delivery == synchronous Overlay.dispatch, bit for bit."""
    srv = OverlayServer(bank_capacity=4, round_kernels=2, max_inflight=2)
    names = ("chebyshev", "poly5", "poly6", "gradient", "mibench") * 2
    reqs = {}
    for i, n in enumerate(names):
        k = kernels[n]
        xs = _xs(k, batch=64 + 32 * (i % 3), seed=i)
        reqs[srv.submit(k, xs, tenant=f"t{i % 3}")] = (k, xs)
    got = dict(srv.as_completed())
    assert set(got) == set(reqs)
    for t, (k, xs) in reqs.items():
        want = _dispatch_oracle([(k, xs)])[0]
        assert len(got[t]) == len(k.dfg.outputs)
        for y, w in zip(got[t], want):
            np.testing.assert_array_equal(np.asarray(y), np.asarray(w))
    assert srv.pending == 0 and srv.bank.n_pinned == 0


def test_flush_and_flush_sync_agree_bitexact(kernels):
    """Pipelined drain and barrier drain serve identical bits."""
    def build():
        srv = OverlayServer(bank_capacity=3, round_kernels=2,
                            max_inflight=3, quantum_tiles=2)
        tickets = {}
        for i in range(14):
            k = kernels[ALL_NAMES[i % 7]]
            xs = _xs(k, batch=48 + 16 * (i % 4), seed=100 + i)
            tickets[srv.submit(k, xs, tenant=f"t{i % 4}")] = (k, xs)
        return srv, tickets

    srv_a, tickets_a = build()
    srv_b, tickets_b = build()
    out_pipe = srv_a.flush()
    out_sync = srv_b.flush_sync()
    assert set(out_pipe) == set(out_sync) == set(tickets_a)
    for t in tickets_a:
        for y, w in zip(out_pipe[t], out_sync[t]):
            np.testing.assert_array_equal(np.asarray(y), np.asarray(w))


def test_staged_pipeline_composes_to_dispatch(kernels):
    """plan -> assemble -> execute -> collect == dispatch, both collect
    modes (lazy device slices and host numpy views)."""
    ov = Overlay()
    bank = ContextBank(4)
    pairs = [(kernels["chebyshev"], _xs(kernels["chebyshev"], 200, 1)),
             (kernels["poly6"], _xs(kernels["poly6"], 33, 2)),
             (kernels["chebyshev"], _xs(kernels["chebyshev"], 64, 3))]
    want = ov.dispatch(ContextBank(4), pairs)
    plan = ov.plan(bank, pairs)
    ys = ov.execute(bank, ov.assemble(plan))
    for host in (False, True):
        got = ov.collect(plan, ys, host=host)
        for g, w in zip(got, want):
            for y, ref in zip(g, w):
                np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


# ----------------------------------------------------------------- ordering
def test_as_completed_yields_rounds_in_completion_order(kernels):
    """Results stream out round by round (arrival order), within a round
    in submission order — not held back to a full-queue barrier."""
    srv = OverlayServer(bank_capacity=4, round_kernels=1, max_inflight=2)
    order = []
    tickets = []
    for i, n in enumerate(("chebyshev", "poly5", "poly6", "gradient")):
        k = kernels[n]
        for j in range(2):
            tickets.append(srv.submit(k, _xs(k, 32, i * 10 + j)))
    for t, _ in srv.as_completed():
        order.append(t)
    # round_kernels=1 => one kernel per round, rounds launch in DRR order,
    # delivery preserves it: tickets grouped pairwise in submission order
    assert order == tickets
    rounds = [srv.record(t)["round"] for t in order]
    assert rounds == sorted(rounds)
    assert len(set(rounds)) == 4


def test_result_blocks_and_claims_once(kernels):
    srv = OverlayServer(bank_capacity=2)
    k = kernels["poly5"]
    xs = _xs(k, 96, 7)
    t = srv.submit(k, xs)
    want = _dispatch_oracle([(k, xs)])[0]
    got = srv.result(t)
    for y, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(y), np.asarray(w))
    with pytest.raises(KeyError):
        srv.result(t)               # a ticket can be claimed once
    with pytest.raises(KeyError):
        srv.result(10_000)          # unknown ticket


def test_submit_during_streaming_is_served(kernels):
    """as_completed picks up requests submitted while iterating."""
    srv = OverlayServer(bank_capacity=2)
    k1, k2 = kernels["chebyshev"], kernels["poly6"]
    t1 = srv.submit(k1, _xs(k1, 32, 0))
    seen = []
    it = srv.as_completed()
    seen.append(next(it)[0])
    t2 = srv.submit(k2, _xs(k2, 32, 1))
    seen.extend(t for t, _ in it)
    assert seen == [t1, t2]


# ----------------------------------------------------------------- fairness
def test_hot_tenant_cannot_starve_cold_tenant(kernels):
    """Bounded wait: a cold tenant's lone request lands within the first
    two rounds even when a hot tenant queued a large multi-kernel backlog
    first (DRR round-robin, one kernel group per round)."""
    srv = OverlayServer(bank_capacity=2, round_kernels=1)
    hot_tickets = []
    for i in range(12):                     # 6 kernels x 2 requests
        k = kernels[ALL_NAMES[i % 6]]
        hot_tickets.append(srv.submit(k, _xs(k, 64, i), tenant="hot"))
    cold_k = kernels[ALL_NAMES[7]]
    cold_ticket = srv.submit(cold_k, _xs(cold_k, 64, 99), tenant="cold")
    srv.flush()
    cold_round = srv.record(cold_ticket)["round"]
    hot_rounds = [srv.record(t)["round"] for t in hot_tickets]
    assert cold_round <= 1, (cold_round, hot_rounds)
    assert max(hot_rounds) >= 5             # backlog really spanned rounds
    # FIFO group order would have served cold LAST
    assert cold_round < max(hot_rounds)


def test_quantum_bounds_hot_tenant_per_round(kernels):
    """With a finite DRR quantum, a hot tenant's backlog on ONE kernel is
    spread across rounds instead of monopolising each round.  Pinned to
    the DRR policy: this is a DRR-semantics test (coalescing/dynamic
    policies deliberately pace differently; see test_sched_policies)."""
    k = kernels["chebyshev"]
    srv = OverlayServer(bank_capacity=4, quantum_tiles=2,
                        round_policy="drr")
    hot = [srv.submit(k, _xs(k, 128, i), tenant="hot") for i in range(8)]
    srv.flush()
    rounds = sorted(srv.record(t)["round"] for t in hot)
    # cost 1 tile each, quantum 2 => at most 2 hot requests per round
    assert max(rounds) >= 3
    for r in set(rounds):
        assert rounds.count(r) <= 2


# ---------------------------------------------------------------- admission
def test_token_bucket_admission_rejects_and_recovers(kernels):
    clock = FakeClock()
    srv = OverlayServer(bank_capacity=2, clock=clock,
                        admission={"metered": (1.0, 2.0)})
    k = kernels["poly5"]
    xs = _xs(k, 128, 0)                     # cost: 1 tile
    srv.submit(k, xs, tenant="metered")
    srv.submit(k, xs, tenant="metered")     # burst of 2 exhausted
    with pytest.raises(AdmissionError) as ei:
        srv.submit(k, xs, tenant="metered")
    assert ei.value.tenant == "metered" and ei.value.retry_after > 0
    # unmetered tenants are unaffected
    srv.submit(k, xs, tenant="free")
    clock.advance(1.0)                      # one token accrues
    srv.submit(k, xs, tenant="metered")
    assert srv.pending == 4
    srv.flush()
    assert srv.pending == 0


def test_default_admission_applies_to_new_tenants(kernels):
    clock = FakeClock()
    srv = OverlayServer(bank_capacity=2, clock=clock,
                        default_admission=(1.0, 1.0))
    k = kernels["poly5"]
    xs = _xs(k, 64, 0)
    srv.submit(k, xs, tenant="anyone")
    with pytest.raises(AdmissionError):
        srv.submit(k, xs, tenant="anyone")
    srv.submit(k, xs, tenant="someone-else")    # separate bucket
    srv.flush()


def test_token_bucket_unit():
    clock = FakeClock()
    tb = TokenBucket(rate=2.0, burst=4.0, clock=clock)
    assert tb.try_acquire(4.0) and not tb.try_acquire(1.0)
    assert tb.retry_after(1.0) == pytest.approx(0.5)
    clock.advance(0.5)
    assert tb.try_acquire(1.0)
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0)


# ------------------------------------------------------------------ pinning
def test_pinned_context_survives_lru_pressure(kernels):
    bank = ContextBank(capacity=2)
    k_pin = kernels["chebyshev"]
    bank.pin(k_pin)
    # churn 3 other kernels through the remaining slot
    for n in ("poly5", "poly6", "gradient"):
        bank.load(kernels[n])
        assert k_pin in bank                # never evicted
    assert bank.n_evictions == 2
    assert bank.evictable_capacity() == 1
    bank.unpin(k_pin)
    assert bank.evictable_capacity() == 2
    # now the (LRU) former pin is evictable again
    bank.load(kernels["mibench"])
    assert k_pin not in bank


def test_all_pinned_bank_raises_instead_of_corrupting(kernels):
    bank = ContextBank(capacity=2)
    bank.pin(kernels["chebyshev"])
    bank.pin(kernels["poly5"])
    with pytest.raises(BankError):
        bank.load(kernels["poly6"])
    # refcounted: double pin needs double unpin
    bank.pin(kernels["chebyshev"])
    bank.unpin(kernels["chebyshev"])
    with pytest.raises(BankError):
        bank.load(kernels["poly6"])
    bank.unpin(kernels["chebyshev"])
    bank.unpin(kernels["poly5"])
    bank.load(kernels["poly6"])             # evictable again
    with pytest.raises(BankError):
        bank.unpin(kernels["poly6"])        # unpin without pin


def test_engine_pins_during_flight_and_releases(kernels):
    """The server pins each round's contexts while in flight and leaves a
    clean bank afterwards, even under eviction pressure."""
    srv = OverlayServer(bank_capacity=2, round_kernels=1, max_inflight=2)
    for i in range(8):
        k = kernels[ALL_NAMES[i % 4]]
        srv.submit(k, _xs(k, 64, i))
    results = srv.flush()
    assert len(results) == 8
    assert srv.bank.n_pinned == 0
    assert srv.bank.n_evictions >= 2
    # served correctly despite churn
    for t, outs in results.items():
        assert all(np.isfinite(np.asarray(y)).all() for y in outs)


def test_round_mixing_resident_and_new_kernels_under_pressure(kernels):
    """Regression: a round containing a resident-but-unpinned kernel plus a
    new kernel, while another round is in flight, must retire/retry — not
    crash with BankError or leak pins."""
    srv = OverlayServer(bank_capacity=3, round_kernels=2, max_inflight=2)
    a, b, c, d = (kernels[n] for n in ("chebyshev", "poly5", "poly6",
                                       "gradient"))
    srv.submit(a, _xs(a, 64, 0))
    srv.flush()                             # A resident, unpinned
    for k, s in ((c, 1), (d, 2), (a, 3), (b, 4)):
        srv.submit(k, _xs(k, 64, s))
    got = dict(srv.as_completed())          # round {C,D} then round {A,B}
    assert len(got) == 4
    assert srv.bank.n_pinned == 0


def test_plan_bankerror_unwinds_pins(kernels):
    """A failed pinned plan must not leak pin refcounts."""
    ov = Overlay()
    bank = ContextBank(capacity=2)
    bank.pin(kernels["chebyshev"])
    bank.pin(kernels["poly5"])
    pairs = [(kernels["poly6"], _xs(kernels["poly6"], 32, 0)),
             (kernels["gradient"], _xs(kernels["gradient"], 32, 1))]
    with pytest.raises(BankError):
        ov.plan(bank, pairs, pin=True)
    assert bank.n_pinned == 2               # only the pre-existing pins


def test_flush_sync_delivers_inflight_rounds(kernels):
    """flush_sync after pipelined use must deliver rounds already in
    flight (no dropped tickets, no leaked pins)."""
    srv = OverlayServer(bank_capacity=4, round_kernels=1, max_inflight=2)
    tickets = []
    for i, n in enumerate(("chebyshev", "poly5", "poly6")):
        k = kernels[n]
        tickets.append(srv.submit(k, _xs(k, 32, i)))
    srv.result(tickets[0])                  # leaves a round in flight
    out = srv.flush_sync()
    assert set(out) == set(tickets[1:])
    assert srv.pending == 0 and srv.bank.n_pinned == 0


def test_quantum_must_be_positive(kernels):
    with pytest.raises(ValueError):
        OverlayServer(bank_capacity=2, quantum_tiles=0)
    with pytest.raises(ValueError):
        OverlayServer(bank_capacity=2, quantum_tiles=-1)
    with pytest.raises(ValueError):
        OverlayServer(bank_capacity=2, round_kernels=0)


def test_same_tenant_old_request_not_starved_by_hot_kernel(kernels):
    """Regression: within one tenant, an old request for a cold kernel
    must not be starved by a continuous stream of hot-kernel traffic —
    untaken requests keep their arrival order in the queue."""
    srv = OverlayServer(bank_capacity=4, round_kernels=1, max_inflight=1)
    a, b = kernels["chebyshev"], kernels["poly5"]
    srv.submit(a, _xs(a, 32, 0))
    t_b = srv.submit(b, _xs(b, 32, 1))
    served = []
    it = srv.as_completed()
    for i in range(8):
        t, _ = next(it)
        served.append(t)
        if t == t_b:
            break
        srv.submit(a, _xs(a, 32, 10 + i))   # sustained hot-kernel load
    assert t_b in served and served.index(t_b) <= 2, served


def test_reset_metrics_keeps_unclaimed_results(kernels):
    """Regression: reset_metrics must not orphan delivered-but-unclaimed
    results — their tickets stay claimable with telemetry intact."""
    srv = OverlayServer(bank_capacity=2, round_kernels=1, max_inflight=2)
    k1, k2 = kernels["chebyshev"], kernels["poly5"]
    xs1 = _xs(k1, 32, 0)
    t1 = srv.submit(k1, xs1)
    t2 = srv.submit(k2, _xs(k2, 32, 1))
    srv.result(t2)                 # delivers t1's round too, unclaimed
    srv.reset_metrics()
    out1 = srv.result(t1)          # must not raise KeyError
    want = _dispatch_oracle([(k1, xs1)])[0]
    for y, w in zip(out1, want):
        np.testing.assert_array_equal(np.asarray(y), np.asarray(w))


def test_metrics_window_bounds_record_history(kernels):
    """Telemetry for claimed tickets is pruned beyond metrics_window."""
    srv = OverlayServer(bank_capacity=2, metrics_window=4)
    k = kernels["chebyshev"]
    for i in range(10):
        srv.submit(k, _xs(k, 32, i))
    srv.flush()
    assert len(srv.latencies()) <= 4
    assert len(srv._records) <= 4


def test_drained_tenant_flows_are_pruned(kernels):
    """Per-tenant flow state must not accumulate over the server's life
    (unbounded tenant-label spaces)."""
    srv = OverlayServer(bank_capacity=2)
    k = kernels["chebyshev"]
    for i in range(20):
        srv.submit(k, _xs(k, 32, i), tenant=f"one-shot-{i}")
    srv.flush()
    assert len(srv._flows) == 0 and len(srv._rr) == 0
    # pruning must not break a tenant that comes back
    t = srv.submit(k, _xs(k, 32, 99), tenant="one-shot-3")
    assert len(srv.flush()) == 1 and srv.record(t)["tenant"] == "one-shot-3"


def test_admission_cost_above_burst_is_unsatisfiable(kernels):
    """A request larger than the bucket burst reports retry_after=inf —
    callers must not retry-livelock on it."""
    import math
    clock = FakeClock()
    srv = OverlayServer(bank_capacity=2, tile=128, clock=clock,
                        admission={"t": (1.0, 4.0)})
    k = kernels["poly5"]
    with pytest.raises(AdmissionError) as ei:
        srv.submit(k, _xs(k, 8 * 128, 0), tenant="t")   # cost 8 > burst 4
    assert math.isinf(ei.value.retry_after)


def test_bank_prefetch_warms_working_set(kernels):
    bank = ContextBank(capacity=4)
    slots = bank.prefetch([kernels[n] for n in ("chebyshev", "poly5",
                                                "poly6")])
    assert sorted(slots) == [0, 1, 2]
    assert all(kernels[n] in bank for n in ("chebyshev", "poly5", "poly6"))
    # prefetching again is pure LRU touches: no load, and no hit, since
    # hits count only a round's lookups of its kernel groups
    assert bank.prefetch([kernels["poly5"]]) == [1]
    assert bank.n_loads == 3 and bank.n_hits == 0


def test_empty_and_zero_length_requests(kernels):
    srv = OverlayServer(bank_capacity=2)
    assert srv.flush() == {}
    k = kernels["chebyshev"]
    t0 = srv.submit(k, [np.zeros(0, np.float32)])
    t1 = srv.submit(k, _xs(k, 64, 0))
    out = srv.flush()
    assert np.shape(out[t0][0]) == (0,) and np.shape(out[t1][0]) == (64,)
