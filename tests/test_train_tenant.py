"""Training-as-a-tenant differential suite (PR 10).

Locks down the co-scheduling contract from three sides:

1. DIFFERENTIAL BIT-IDENTITY — a training run sliced into micro-rounds
   and co-scheduled through a serving engine (``TrainingTenant``) is
   bit-identical — params, opt_state, loss trace — to a standalone
   ``run_training`` loop on the same seed, under every round policy and
   under fleet grow/drain churn.
2. EXACTLY-ONCE PREEMPT/RESUME — random preemption points (seeded
   ``should_yield`` hooks, 4-seed matrix) never lose or double-apply a
   step: optimizer state, error-feedback ``ef``, and the data cursor
   survive every yield.
3. STARVATION IS ONE-DIRECTIONAL — saturated serving drives training
   throughput to zero (no bulk round forms while a latency flow is
   queued) while every serving request is still delivered; training
   never delays a latency round, so serving p99 under co-scheduling
   stays within a calibrated bound of the dedicated-engine control.

Plus the seed-matrix determinism regression for ``runtime/steps.py``
and the CLI-vs-library trace differential for ``launch/train.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.bank import ContextBank
from repro.core.overlay import Overlay, compile_program
from repro.core.paper_bench import benchmark
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.launch.serve import OverlayServer, ShardedOverlayServer
from repro.launch.train import run_training
from repro.launch.trainer_tenant import TrainingTenant
from repro.models import init_params
from repro.runtime import optim as O
from repro.runtime.steps import make_train_step
from repro.sched import (BULK_PREFIX, CoalescingPolicy, DeficitRoundRobin,
                         DynamicTilePolicy, PreemptibleTier, WorkRequest,
                         make_round_policy)
from repro.telemetry import check_stats

ROOT = pathlib.Path(__file__).resolve().parent.parent

POLICIES = {
    "drr": lambda: DeficitRoundRobin(quantum_tiles=2.0),
    "coalesce": lambda: CoalescingPolicy(quantum_tiles=2.0,
                                         coalesce_tiles=8),
    "dynamic": lambda: DynamicTilePolicy(quantum_tiles=2.0, init_tiles=8,
                                         min_tiles=2),
}

STEPS = 8


@pytest.fixture(scope="module")
def cfgs():
    cfg = get_smoke_config("deepseek-7b")
    oc = O.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    dc = DataConfig(global_batch=2, seq_len=32, vocab=cfg.vocab)
    return cfg, oc, dc


@pytest.fixture(scope="module")
def step_fn(cfgs):
    """One shared jit: every arm of the differential reuses the same
    compiled step, so the comparison isolates the SCHEDULING."""
    cfg, oc, _ = cfgs
    return jax.jit(make_train_step(cfg, oc))


@pytest.fixture(scope="module")
def step_fn_compress(cfgs):
    cfg, oc, _ = cfgs
    return jax.jit(make_train_step(cfg, oc, compress_grads=True))


def _standalone(cfgs, *, steps=STEPS, step_fn=None, compress=False):
    """The reference: a plain ``run_training`` loop, no engine."""
    cfg, oc, dc = cfgs
    params, opt, losses = None, None, []
    for rec in run_training(cfg, oc, dc, steps=steps, yield_every=1,
                            compress_grads=compress, step_fn=step_fn):
        params, opt = rec["params"], rec["opt_state"]
        losses.append(rec["loss"])
    return params, opt, losses


@pytest.fixture(scope="module")
def ref(cfgs, step_fn):
    return _standalone(cfgs, step_fn=step_fn)


@pytest.fixture(scope="module")
def ref_compress(cfgs, step_fn_compress):
    return _standalone(cfgs, step_fn=step_fn_compress, compress=True)


@pytest.fixture(scope="module")
def kernel():
    return compile_program(benchmark("poly5"))


def _xs(kernel, batch, seed):
    rng = np.random.RandomState(seed)
    return [rng.uniform(-2, 2, (batch,)).astype(np.float32)
            for _ in kernel.dfg.inputs]


def _oracle(k, xs):
    [want] = Overlay().dispatch(ContextBank(4), [(k, xs)])
    return want


def _assert_tree_equal(got, want):
    la, lb = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _drive_cosched(server, tenant, kernel, *, beats_cap=64, serve=True):
    """Tick the tenant between serving beats until training finishes;
    every serving request must come back same-beat and bit-exact."""
    beat = 0
    lat = []
    while not tenant.done:
        if serve:
            xs = _xs(kernel, 4, beat)
            t = server.submit(kernel, xs, tenant="alice")
        tenant.tick()
        res = server.flush()
        if serve:
            assert t in res, "serving request starved by training"
            np.testing.assert_array_equal(np.asarray(res[t][0]),
                                          np.asarray(_oracle(kernel, xs)[0]))
        beat += 1
        assert beat < beats_cap, "co-scheduled run failed to finish"
        lat.append(beat)
    return beat


# =================================================== PreemptibleTier units


def test_preemptible_tier_construction():
    tier = PreemptibleTier()                      # default inner DRR
    assert isinstance(tier.inner, DeficitRoundRobin)
    tier = PreemptibleTier("coalesce", quantum_tiles=4.0)
    assert isinstance(tier.inner, CoalescingPolicy)
    inner = DynamicTilePolicy(quantum_tiles=2.0, init_tiles=8, min_tiles=2)
    assert PreemptibleTier(inner).inner is inner
    with pytest.raises(ValueError):
        PreemptibleTier(inner, quantum_tiles=4.0)  # instance + knob
    with pytest.raises(ValueError):
        PreemptibleTier(PreemptibleTier())         # no double wrap


def test_preemptible_tier_is_bulk():
    tier = PreemptibleTier(bulk_tenants={"batchq"})
    assert tier.is_bulk("batchq")
    assert tier.is_bulk(BULK_PREFIX + "anything")
    assert not tier.is_bulk("alice")
    tier.add_bulk({"alice"})
    assert tier.is_bulk("alice")


def test_preemptible_tier_stats_and_quantum():
    tier = PreemptibleTier(DeficitRoundRobin(
        quantum_tiles=2.0, tenant_quanta={"bulk:train": 0.5}))
    assert tier.quantum_for("bulk:train") == 0.5
    s = tier.stats()
    assert s["tier_policy"] == "DeficitRoundRobin"
    assert s["latency_rounds"] == 0 and s["bulk_rounds"] == 0


def test_make_preemptible_idempotent(kernel):
    srv = OverlayServer(bank_capacity=4)
    tier = srv.make_preemptible(bulk_tenants={"b1"})
    tier2 = srv.make_preemptible(bulk_tenants={"b2"})
    assert tier is tier2 and tier is srv.round_policy
    assert tier.is_bulk("b1") and tier.is_bulk("b2")


# ====================================================== submit_work engine


def test_submit_work_mixed_round(kernel):
    srv = OverlayServer(bank_capacity=4)
    ran = []
    xs = _xs(kernel, 4, 0)
    t_k = srv.submit(kernel, xs, tenant="alice")
    t_w = srv.submit_work(lambda: ran.append(1) or "done", tenant="bulk:w")
    res = srv.flush()
    assert res[t_w] == "done" and ran == [1]
    np.testing.assert_array_equal(np.asarray(res[t_k][0]),
                                  np.asarray(_oracle(kernel, xs)[0]))
    check_stats("engine", srv.stats())


def test_submit_work_flush_sync_parity(kernel):
    """The barrier oracle drains work requests identically."""
    outs = {}
    for drain in ("flush", "flush_sync"):
        srv = OverlayServer(bank_capacity=4)
        xs = _xs(kernel, 4, 1)
        t_k = srv.submit(kernel, xs, tenant="alice")
        t_w = srv.submit_work(lambda: 42, tenant="bulk:w")
        res = getattr(srv, drain)()
        outs[drain] = (np.asarray(res[t_k][0]), res[t_w])
    np.testing.assert_array_equal(outs["flush"][0], outs["flush_sync"][0])
    assert outs["flush"][1] == outs["flush_sync"][1] == 42


def test_work_request_exported():
    r = WorkRequest(ticket=0, kernel=None, xs=[], tenant="bulk:x",
                    key=None, cost=1, t_submit=0.0, fn=lambda: 1,
                    label="probe")
    assert r.name == "probe" and r.batch == 0


# ============================================== differential: bit-identity


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_cosched_bit_identity_policies(cfgs, step_fn, ref, kernel, policy):
    """Co-scheduled == standalone, bit for bit, under every round policy,
    with latency traffic interleaved every beat."""
    cfg, oc, dc = cfgs
    srv = OverlayServer(bank_capacity=8, round_policy=POLICIES[policy]())
    tenant = TrainingTenant(srv, cfg, oc, dc, steps=STEPS, yield_every=3,
                            step_fn=step_fn)
    assert isinstance(srv.round_policy, PreemptibleTier)
    _drive_cosched(srv, tenant, kernel)
    ref_params, ref_opt, ref_losses = ref
    assert tenant.losses == ref_losses
    assert tenant.step_trace == list(range(STEPS))
    _assert_tree_equal(tenant.params, ref_params)
    _assert_tree_equal(tenant.opt_state, ref_opt)
    st = tenant.stats()
    check_stats("train", st)
    assert st["steps"] == STEPS and st["done"]


def test_cosched_bit_identity_fleet_churn(cfgs, step_fn, ref, kernel):
    """Same differential on a sharded fleet with forced add_replica /
    drain_replica churn between micro-rounds."""
    cfg, oc, dc = cfgs
    fleet = ShardedOverlayServer(n_replicas=2, bank_capacity=6)
    tenant = TrainingTenant(fleet, cfg, oc, dc, steps=STEPS, yield_every=2,
                            step_fn=step_fn)
    beat = 0
    while not tenant.done:
        if beat == 1:
            fleet.add_replica()
        if beat == 3:
            fleet.drain_replica(0)
        xs = _xs(kernel, 4, beat)
        t = fleet.submit(kernel, xs, tenant="alice")
        tenant.tick()
        res = fleet.flush()
        assert t in res
        np.testing.assert_array_equal(np.asarray(res[t][0]),
                                      np.asarray(_oracle(kernel, xs)[0]))
        beat += 1
        assert beat < 64
    ref_params, ref_opt, ref_losses = ref
    assert tenant.losses == ref_losses
    _assert_tree_equal(tenant.params, ref_params)
    _assert_tree_equal(tenant.opt_state, ref_opt)
    check_stats("fleet", fleet.stats())
    # replicas added after make_preemptible inherit the tier
    for rep in fleet.replicas:
        assert isinstance(rep.round_policy, PreemptibleTier)


# ===================================== exactly-once preempt/resume property


def _random_yield(seed):
    """Seeded preemption schedule: always preempt at the first poll
    (guarantees >= 1 preemption), then coin-flip every boundary."""
    rng = np.random.RandomState(seed)
    state = {"first": True}

    def should_yield():
        if state["first"]:
            state["first"] = False
            return True
        return bool(rng.rand() < 0.5)

    return should_yield


@pytest.mark.parametrize("seed", [0, 1, 2, 3],
                         ids=[f"seed{i}" for i in range(4)])
def test_preempt_resume_exactly_once(cfgs, step_fn, ref, kernel, seed):
    """Random preemption points never lose or double-apply a step:
    params/opt_state land bit-identical to the standalone run, the step
    trace is exactly 0..N-1 once each, and every preemption is paired
    with exactly one resume."""
    cfg, oc, dc = cfgs
    srv = OverlayServer(bank_capacity=8)
    tenant = TrainingTenant(srv, cfg, oc, dc, steps=STEPS, yield_every=4,
                            step_fn=step_fn, should_yield=_random_yield(seed))
    _drive_cosched(srv, tenant, kernel)
    ref_params, ref_opt, ref_losses = ref
    assert tenant.step_trace == list(range(STEPS)), "step lost or doubled"
    assert tenant.losses == ref_losses
    _assert_tree_equal(tenant.params, ref_params)
    _assert_tree_equal(tenant.opt_state, ref_opt)
    st = tenant.stats()
    check_stats("train", st)
    assert st["preemptions"] >= 1
    assert st["resumes"] == st["preemptions"], "unpaired preempt/resume"
    assert tenant.cursor == SyntheticCorpus(dc).cursor(STEPS)


@pytest.mark.parametrize("seed", [0, 1, 2, 3],
                         ids=[f"seed{i}" for i in range(4)])
def test_preempt_resume_exactly_once_compressed(cfgs, step_fn_compress,
                                                ref_compress, kernel, seed):
    """Same property with int8 grad compression: the error-feedback
    state in opt_state['ef'] survives every preempt/resume."""
    cfg, oc, dc = cfgs
    srv = OverlayServer(bank_capacity=8)
    tenant = TrainingTenant(srv, cfg, oc, dc, steps=STEPS, yield_every=4,
                            compress_grads=True, step_fn=step_fn_compress,
                            should_yield=_random_yield(seed))
    _drive_cosched(srv, tenant, kernel)
    ref_params, ref_opt, ref_losses = ref_compress
    assert tenant.step_trace == list(range(STEPS))
    assert tenant.losses == ref_losses
    assert "ef" in tenant.opt_state, "error-feedback state dropped"
    _assert_tree_equal(tenant.params, ref_params)
    _assert_tree_equal(tenant.opt_state, ref_opt)
    assert tenant.stats()["resumes"] == tenant.stats()["preemptions"] >= 1


# ================================================= starvation is one-sided


def test_serving_starves_training_never_reverse(cfgs, step_fn, kernel):
    """While a latency flow is continuously backlogged NO bulk round
    forms — training throughput is exactly zero — yet every serving
    request is delivered.  When the pressure stops, training completes.
    max_inflight=1 keeps launch/retire strictly alternating so the
    starvation window is exact."""
    cfg, oc, dc = cfgs
    srv = OverlayServer(bank_capacity=8, max_inflight=1)
    tenant = TrainingTenant(srv, cfg, oc, dc, steps=4, yield_every=2,
                            step_fn=step_fn)
    tenant.tick()                      # micro-round queued on the bulk tier
    tier = srv.round_policy
    tickets = [srv.submit(kernel, _xs(kernel, 4, i), tenant="alice")
               for i in range(2)]
    for i in range(12):
        # keep the latency queue NON-EMPTY across every form_round call
        tickets.append(srv.submit(kernel, _xs(kernel, 4, 10 + i),
                                  tenant="alice"))
        srv.pump_once()
        assert tier.n_bulk_rounds == 0, "bulk round formed under backlog"
        assert tenant.stats()["steps"] == 0, "training ran while starved"
    # serving made progress the whole time training was starved
    assert int(srv.telemetry.counter("engine.rounds")) >= 10
    res = srv.flush()                  # drain the tail (incl. the bulk round)
    assert all(t in res for t in tickets), "serving starved — never allowed"
    # pressure gone: training runs to completion
    tenant.run()
    assert tenant.done and tenant.stats()["steps"] == 4
    assert tier.n_bulk_rounds >= 1


def test_serving_p99_bounded_under_training(cfgs, step_fn, kernel):
    """Calibrated p99 bound: co-scheduled serving latency stays within a
    generous multiple of the dedicated-engine control (the tight <10%
    assertion lives in benchmarks/train_serve_study.py at matched load;
    this guards against structural regressions — e.g. a latency round
    retiring behind a bulk launch)."""
    cfg, oc, dc = cfgs

    def drive(with_training):
        srv = OverlayServer(bank_capacity=8, max_inflight=1)
        tenant = None
        if with_training:
            tenant = TrainingTenant(srv, cfg, oc, dc, steps=6,
                                    yield_every=2, step_fn=step_fn)
        for beat in range(12):
            xs = _xs(kernel, 4, beat)
            t = srv.submit(kernel, xs, tenant="alice")
            if tenant is not None:
                tenant.tick()
            res = srv.flush()
            assert t in res
        return srv.tenant_latency_percentiles()["alice"]["p99"]

    p99_dedicated = drive(with_training=False)
    p99_cosched = drive(with_training=True)
    assert p99_cosched <= p99_dedicated * 10 + 0.25, (
        f"serving p99 {p99_cosched:.4f}s vs dedicated "
        f"{p99_dedicated:.4f}s — training is delaying latency rounds")


# ============================================ telemetry: train.* counters


def test_train_counters_fan_out_to_server_sink(cfgs, step_fn):
    """The tenant's MultiSink writes train.* into the engine's sink too,
    so fleet-level stores see training alongside serving."""
    cfg, oc, dc = cfgs
    srv = OverlayServer(bank_capacity=4)
    tenant = TrainingTenant(srv, cfg, oc, dc, steps=2, yield_every=2,
                            step_fn=step_fn)
    tenant.run()
    assert int(srv.telemetry.counter("train.steps")) == 2
    assert int(tenant.telemetry.counter("train.steps")) == 2
    check_stats("train", tenant.stats())


# ====================================== seed-matrix determinism regression


@pytest.mark.parametrize("variant", ["plain", "compress", "mixed"])
def test_seed_matrix_step_determinism(cfgs, variant):
    """runtime/steps.py regression: same seed -> bit-identical params
    after N steps, with and without compress_grads / mixed precision."""
    cfg, oc, dc = cfgs
    compress = variant == "compress"
    mixed = variant == "mixed"

    def one_run():
        params = init_params(cfg, jax.random.PRNGKey(0))
        if mixed:
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16), params)
        opt = O.init_opt_mixed(params) if mixed else O.init_opt(params)
        fn = jax.jit(make_train_step(cfg, oc, compress_grads=compress,
                                     mixed=mixed))
        last = None
        for rec in run_training(cfg, oc, dc, steps=4, params=params,
                                opt_state=opt, compress_grads=compress,
                                mixed=mixed, step_fn=fn):
            last = rec
        return last["params"], last["opt_state"], last["loss"]

    p1, o1, l1 = one_run()
    p2, o2, l2 = one_run()
    assert l1 == l2
    _assert_tree_equal(p1, p2)
    _assert_tree_equal(o1, o2)


# ============================================== CLI-vs-library differential


def test_cli_and_library_traces_identical(cfgs, tmp_path):
    """launch/train.py satellite: the CLI (subprocess, --trace-out) and
    the importable run_training loop produce IDENTICAL step/loss traces
    — the refactor left no behavioural fork between the two paths."""
    steps, batch, seq, lr = 4, 2, 32, 1e-3
    trace_file = tmp_path / "trace.json"
    # the CLI turns on the persistent compile cache: keep it out of the
    # checkout
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_DEVICES", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "deepseek-7b",
         "--smoke", "--steps", str(steps), "--batch", str(batch),
         "--seq", str(seq), "--lr", str(lr),
         "--trace-out", str(trace_file)],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=str(ROOT))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    got = json.loads(trace_file.read_text())

    cfg = get_smoke_config("deepseek-7b")
    oc = O.OptConfig(lr=lr, total_steps=max(steps, 10),
                     warmup_steps=max(2, steps // 20))
    dc = DataConfig(global_batch=batch, seq_len=seq, vocab=cfg.vocab)
    want = {"steps": [], "losses": []}
    for rec in run_training(cfg, oc, dc, steps=steps):
        want["steps"].append(rec["step"])
        want["losses"].append(rec["loss"])
    assert got["steps"] == want["steps"]
    assert got["losses"] == want["losses"], (
        "CLI and library step traces diverged")
