"""Multi-tenant serving engine tests: ContextBank + vm_exec_multi + dispatch.

Covers the PR acceptance bar: a bank of >= 8 resident kernels serves a
mixed-kernel request batch through a SINGLE compiled vm_exec_multi
executable (zero retraces after warmup, asserted on the jit cache), with
every output matching the dfg_eval oracle; plus LRU eviction / slot-id
reuse semantics and the Pallas multi-context path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vm as vm_mod
from repro.core.bank import BankError, ContextBank, context_key
from repro.core.frontend import build_dfg
from repro.core.overlay import Overlay, compile_program
from repro.core.paper_bench import BENCH_NAMES, benchmark
from repro.core.vm import dfg_eval
from repro.launch.serve import OverlayServer

ALL_NAMES = BENCH_NAMES + ("gradient",)


@pytest.fixture(scope="module")
def kernels():
    return {n: compile_program(benchmark(n)) for n in ALL_NAMES}


def _requests(kernels, names, batches, seed=0):
    rng = np.random.RandomState(seed)
    reqs = []
    for n, b in zip(names, batches):
        k = kernels[n]
        xs = [rng.uniform(-2, 2, (b,)).astype(np.float32)
              for _ in k.dfg.inputs]
        reqs.append((k, xs))
    return reqs


def _check_against_oracle(reqs, outs, rtol=1e-6, atol=1e-6):
    for (k, xs), ys in zip(reqs, outs):
        assert len(ys) == len(k.dfg.outputs)
        ref = dfg_eval(k.dfg, {m: jnp.asarray(v)
                               for m, v in zip(k.dfg.inputs, xs)})
        for o, y in zip(k.dfg.outputs, ys):
            assert y.shape == np.shape(xs[0])
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref[o]),
                                       rtol=rtol, atol=atol)


# ------------------------------------------------------------- acceptance bar
def test_bank_of_9_serves_mixed_batch_single_executable(kernels):
    """>= 8 resident kernels, one executor, zero retraces after warmup."""
    ov = Overlay()
    bank = ov.load_many(kernels.values(), capacity=len(kernels))
    assert len(bank) == 9 >= 8
    names = list(ALL_NAMES) * 2
    batches = [64, 100, 128, 300, 17, 256, 90, 128, 1][::-1] + [128] * 9
    reqs = _requests(kernels, names, batches)
    outs = ov.dispatch(bank, reqs)          # warmup launch
    _check_against_oracle(reqs, outs)
    n0 = vm_mod.vm_exec_multi._cache_size()
    reqs2 = _requests(kernels, names, batches, seed=7)
    outs2 = ov.dispatch(bank, reqs2)
    _check_against_oracle(reqs2, outs2)
    assert vm_mod.vm_exec_multi._cache_size() == n0, \
        "mixed-kernel dispatch retraced after warmup"


def test_dispatch_pallas_backend_matches_oracle(kernels):
    names = ("chebyshev", "poly6", "gradient", "mibench", "qspline")
    ov = Overlay(backend="pallas")
    bank = ov.load_many([kernels[n] for n in names])
    reqs = _requests(kernels, names, [200, 64, 128, 33, 256], seed=3)
    outs = ov.dispatch(bank, reqs)
    _check_against_oracle(reqs, outs, rtol=1e-5, atol=1e-5)
    assert ov.executor == "pallas-interpret"      # CPU devices only


def test_pallas_refuses_bank_over_smem_limit_at_construction():
    """A bank the kernel cannot hold in SMEM is refused before any
    compile, naming the limit; the largest that fits is accepted."""
    from repro.kernels.tmfu.kernel import MAX_BANK_CAPACITY
    ov = Overlay(backend="pallas")
    assert ov.load_many((), capacity=MAX_BANK_CAPACITY).capacity == \
        MAX_BANK_CAPACITY
    with pytest.raises(ValueError, match=f"at most {MAX_BANK_CAPACITY}"):
        ov.load_many((), capacity=MAX_BANK_CAPACITY + 1)
    with pytest.raises(ValueError, match="SMEM"):
        OverlayServer(bank_capacity=MAX_BANK_CAPACITY + 1, backend="pallas")
    # the jnp VM keeps its bank in HBM: no such limit
    assert Overlay().load_many((), capacity=MAX_BANK_CAPACITY + 1).capacity \
        == MAX_BANK_CAPACITY + 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_pallas_refuses_packed_dtypes_at_construction(dtype):
    with pytest.raises(ValueError, match="32-bit"):
        Overlay(backend="pallas", dtype=dtype)
    with pytest.raises(ValueError, match="32-bit"):
        OverlayServer(backend="pallas", dtype=dtype)
    assert Overlay(dtype=dtype).executor == "jnp"


def test_vm_exec_multi_agrees_with_vm_exec(kernels):
    """Gathering context c from the bank == running context c standalone."""
    ov = Overlay()
    bank = ov.load_many(kernels.values(), capacity=len(kernels))
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.uniform(-2, 2, (len(ALL_NAMES), 32, 128))
                    .astype(np.float32))
    ids = jnp.arange(len(ALL_NAMES), dtype=jnp.int32)
    ys = vm_mod.vm_exec_multi(bank.tree(), bank.out_idx, ids, x)
    for slot in range(len(ALL_NAMES)):
        k = kernels[bank.meta(slot)["name"]]
        ctx = ov.load(k)
        want = vm_mod.vm_exec(ctx.tree(), ctx.out_idx, x[slot])
        np.testing.assert_allclose(
            np.asarray(ys[slot, :ctx.n_outputs]), np.asarray(want),
            rtol=1e-6, atol=1e-6)


# ------------------------------------------------------------ LRU / eviction
def test_bank_eviction_is_lru_and_reuses_slots(kernels):
    bank = ContextBank(capacity=2)
    s_a = bank.load(kernels["chebyshev"])
    s_b = bank.load(kernels["poly5"])
    assert bank.resident == ("chebyshev", "poly5")
    # touch chebyshev so poly5 becomes LRU
    assert bank.load(kernels["chebyshev"]) == s_a
    s_c = bank.load(kernels["poly6"])       # evicts poly5, reuses its slot
    assert s_c == s_b
    assert "poly5" not in bank and bank.n_evictions == 1
    assert bank.resident == ("chebyshev", "poly6")
    # reloading the evicted kernel evicts the (new) LRU = chebyshev
    s_d = bank.load(kernels["poly5"])
    assert s_d == s_a and bank.n_evictions == 2
    assert bank.resident == ("poly6", "poly5")


def test_bank_eviction_keeps_results_correct(kernels):
    """After an evict + reload cycle the served numerics stay oracle-exact."""
    ov = Overlay()
    bank = ContextBank(capacity=2)
    for round_names in (("chebyshev", "poly5"), ("poly6", "gradient"),
                        ("chebyshev", "poly6")):
        reqs = _requests(kernels, round_names, [128, 64], seed=11)
        _check_against_oracle(reqs, ov.dispatch(bank, reqs))
    assert bank.n_evictions >= 2


def test_bank_capacity_and_output_guards(kernels):
    with pytest.raises(BankError):
        ContextBank(capacity=0)
    bank = ContextBank(capacity=1, max_outputs=0)
    with pytest.raises(BankError):
        bank.load(kernels["chebyshev"])
    ov = Overlay()
    small = ov.load_many([kernels["chebyshev"], kernels["poly5"]],
                         capacity=2)
    reqs = _requests(kernels, ("chebyshev", "poly5", "poly6"),
                     [64, 64, 64])
    with pytest.raises(BankError):
        ov.dispatch(small, reqs)            # 3 kernels > capacity 2


def test_same_name_different_program_are_distinct_tenants():
    """Residency keys on context CONTENT: a name collision must never serve
    the wrong program."""
    k_add = compile_program(build_dfg("same", ["x"], "y = x + x", ["y"]))
    k_mul = compile_program(build_dfg("same", ["x"], "y = x * x", ["y"]))
    assert context_key(k_add) != context_key(k_mul)
    ov = Overlay()
    bank = ContextBank(capacity=4)
    xs = [np.full(64, 3.0, np.float32)]
    outs = ov.dispatch(bank, [(k_add, xs), (k_mul, xs)])
    np.testing.assert_array_equal(np.asarray(outs[0][0]), np.full(64, 6.0))
    np.testing.assert_array_equal(np.asarray(outs[1][0]), np.full(64, 9.0))
    assert len(bank) == 2 and bank.resident == ("same", "same")
    # content-identical reload is still a hit, not a new tenant
    assert bank.load(k_add) == bank.load(compile_program(
        build_dfg("same", ["x"], "y = x + x", ["y"])))


def test_dispatch_zero_length_requests(kernels):
    """Degenerate empty batches must not crash the dispatcher."""
    ov = Overlay()
    bank = ContextBank(capacity=2)
    k = kernels["chebyshev"]
    empty = [np.zeros(0, np.float32)]
    outs = ov.dispatch(bank, [(k, empty)])
    assert [y.shape for y in outs[0]] == [(0,)]
    # mixed empty + non-empty
    xs = [np.ones(64, np.float32)]
    p5 = kernels["poly5"]
    p5_empty = [np.zeros(0, np.float32) for _ in p5.dfg.inputs]
    outs = ov.dispatch(bank, [(k, empty), (k, xs), (p5, p5_empty)])
    assert outs[0][0].shape == (0,) and outs[2][0].shape == (0,)
    assert outs[1][0].shape == (64,)


def test_eviction_reload_uses_encode_cache(kernels):
    bank = ContextBank(capacity=1)
    bank.load(kernels["chebyshev"])
    bank.load(kernels["poly5"])          # evicts chebyshev
    assert "chebyshev" not in bank
    bank.load(kernels["chebyshev"])      # reload: pure device write
    assert bank.n_evictions == 2
    assert set(k[0] for k in bank._ctx_cache) == {"chebyshev", "poly5"}


# ------------------------------------------------------------- OverlayServer
def test_server_round_robins_working_set_larger_than_bank(kernels):
    srv = OverlayServer(bank_capacity=3)
    rng = np.random.RandomState(13)
    tickets = {}
    for i in range(18):                     # 9 kernels x 2 requests
        k = kernels[ALL_NAMES[i % len(ALL_NAMES)]]
        xs = [rng.uniform(-2, 2, (96,)).astype(np.float32)
              for _ in k.dfg.inputs]
        tickets[srv.submit(k, xs)] = (k, xs)
    results = srv.flush()
    assert srv.pending == 0
    assert set(results) == set(tickets)
    assert srv.n_rounds == 3                # ceil(9 kernels / bank 3)
    assert srv.bank.n_evictions >= 9 - 3
    for t, (k, xs) in tickets.items():
        _check_against_oracle([(k, xs)], [results[t]])


def test_server_stats_and_empty_flush():
    srv = OverlayServer(bank_capacity=2)
    assert srv.flush() == {}
    st = srv.stats()
    assert st["requests"] == 0 and st["capacity"] == 2
