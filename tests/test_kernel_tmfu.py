"""TMFU Pallas kernel vs pure-jnp oracle: shape/dtype sweeps + benchmarks.

Kernels run in interpret mode here on CPU devices; the oracle is ref.py,
cross-checked against the DFG evaluator.  Mosaic compiles of the same
kernels for a described TPU v5e are in tests/test_chip_compile.py, and
``chip_smoke.py`` runs them on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.overlay import Overlay, compile_program
from repro.core.paper_bench import BENCH_NAMES, benchmark
from repro.core.vm import dfg_eval, make_context, pad_inputs
from repro.kernels.tmfu import tmfu_pipeline, tmfu_ref
from repro.kernels.tmfu.ops import _imm_to_i32


def _ctx_and_inputs(name, batch, dtype, seed=0):
    dfg = benchmark(name)
    ctx = make_context(compile_program(dfg).program, dtype=dtype)
    rng = np.random.RandomState(seed)
    if jnp.issubdtype(dtype, jnp.integer):
        xs = [rng.randint(-6, 7, size=(batch,)).astype(np.int32)
              for _ in dfg.inputs]
    else:
        xs = [rng.uniform(-2, 2, (batch,)).astype(np.float32)
              for _ in dfg.inputs]
    x = pad_inputs([jnp.asarray(v, dtype) for v in xs])
    return dfg, ctx, xs, x


@pytest.mark.parametrize("name", BENCH_NAMES + ("gradient",))
def test_kernel_matches_ref_all_benchmarks(name):
    dfg, ctx, xs, x = _ctx_and_inputs(name, 256, jnp.float32)
    got = tmfu_pipeline(ctx, x, block_batch=128, interpret=True)
    ref_rf = tmfu_ref(np.asarray(ctx.op), np.asarray(ctx.src_a),
                      np.asarray(ctx.src_b), np.asarray(ctx.imm), x)
    want = ref_rf[np.asarray(ctx.out_idx)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # and against the DFG semantics
    env = {n: jnp.asarray(v) for n, v in zip(dfg.inputs, xs)}
    oracle = dfg_eval(dfg, env)
    for j, o in enumerate(dfg.outputs):
        np.testing.assert_allclose(np.asarray(got[j]),
                                   np.asarray(oracle[o]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("batch,block", [(128, 128), (384, 128),
                                         (1024, 512), (100, 128),
                                         (777, 256)])
def test_kernel_shape_sweep(batch, block):
    """Odd batches are padded up; results must match the oracle exactly."""
    dfg, ctx, xs, x = _ctx_and_inputs("poly6", batch, jnp.float32, seed=3)
    got = tmfu_pipeline(ctx, x, block_batch=block, interpret=True)
    env = {n: jnp.asarray(v) for n, v in zip(dfg.inputs, xs)}
    oracle = dfg_eval(dfg, env)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(oracle[dfg.outputs[0]]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_kernel_dtype_sweep(dtype):
    dfg, ctx, xs, x = _ctx_and_inputs("mibench", 256, dtype, seed=5)
    got = tmfu_pipeline(ctx, x, block_batch=128, interpret=True)
    ref_rf = tmfu_ref(np.asarray(ctx.op), np.asarray(ctx.src_a),
                      np.asarray(ctx.src_b), np.asarray(ctx.imm), x)
    want = ref_rf[np.asarray(ctx.out_idx)]
    if dtype == jnp.bfloat16:
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_backend_in_overlay():
    """Overlay(backend='pallas') must agree with the jnp VM backend."""
    dfg = benchmark("qspline")
    k = compile_program(dfg)
    rng = np.random.RandomState(11)
    xs = [rng.uniform(-1, 1, (128,)).astype(np.float32) for _ in dfg.inputs]
    ov_jnp = Overlay(backend="jnp")
    ov_pl = Overlay(backend="pallas")
    y1 = ov_jnp(ov_jnp.load(k), xs)
    y2 = ov_pl(ov_pl.load(k), xs)
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(y2[0]),
                               rtol=1e-6)


def test_kernel_traces_and_interpret_lowers():
    """Structural check: abstract-eval/trace of the pallas_call succeeds and
    the interpret path lowers inside jit.

    The CPU backend has no Mosaic lowering (it rejects interpret=False);
    the Mosaic compiles for a described v5e are in test_chip_compile.py.
    """
    dfg, ctx, xs, x = _ctx_and_inputs("chebyshev", 1024, jnp.float32)
    from repro.kernels.tmfu.kernel import tmfu_pipeline_rf

    def f(op, a, b, imm, xx):
        return tmfu_pipeline_rf(op, a, b, imm, xx,
                                block_batch=512, interpret=True)

    args = (ctx.op, ctx.src_a, ctx.src_b, _imm_to_i32(ctx.imm), x)
    shape = jax.eval_shape(f, *args)
    assert shape.shape == (32, 1024)
    txt = jax.jit(f).lower(*args).as_text()
    assert "while" in txt or "func" in txt  # lowered module exists
