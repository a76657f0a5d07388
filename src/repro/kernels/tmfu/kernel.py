"""Pallas TPU kernel: the time-multiplexed FU pipeline (paper Fig. 2/3).

Mapping of the paper's FU onto the TPU memory hierarchy:

  * Instruction memory (32x32 RAM32M)  -> int32 words in SMEM, delivered by
    scalar prefetch (PrefetchScalarGridSpec) so the VPU datapath never
    stalls on instruction fetch — the analogue of the FU's dedicated IM.
  * Register file (32-entry RAM32M)    -> a (32, bt) VMEM scratch buffer;
    'bt' lanes execute the same instruction on independent kernel
    iterations (vectorized pipeline replication, paper Fig. 4).
  * DSP48E1 + config bits, no decoder  -> a branch table on the 5-bit
    opcode field (one predicated region per op); operands gathered by
    dynamic row index (the 5-bit RF addresses).
  * Linear FU->FU interconnect         -> stage loop ping-ponging two VMEM
    buffers: stage s writes its full result stream, which IS stage s+1's
    register file (direct connection, no programmable routing).

The grid tiles the batch; each grid step streams one (32, bt) tile through
all S stages.  Immediates ride in SMEM as int32 bit-patterns of the f32
constants (bitcast back inside the kernel) so every context word stays a
plain 32-bit integer, like the hardware's 40-bit context stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.isa import IM_DEPTH, RF_DEPTH
from repro.core.vm import S_MAX

DEFAULT_BLOCK_BATCH = 512

# --- SMEM budget of the multi-tenant kernel -------------------------------
# Scalar prefetch copies the whole bank (four [N, S * IM] int32 arrays) and
# the round's [G] tile-id vector into SMEM before the first grid step.  On
# a TPU v5e core the compiler reports 1 MiB of SMEM ("Used 1.00M of 1.00M
# smem") and keeps 1.1-1.6 KiB of it for itself.  SMEM tiles a 2-D array
# in (8, 128) words, so the [N, 512] bank costs 4 * 512 * 4 = 8 KiB a
# context with N rounded up to a multiple of 8 (the unflattened
# [N, 16, 32] layout padded every 32-word row to 128 words: 32 KiB a
# context, at most 31 contexts).
#: SMEM of one TPU v5e core, in bytes
SMEM_BYTES = 1 << 20
#: SMEM the compiler keeps beside the prefetched operands (rounded up)
SMEM_RESERVED_BYTES = 2 * 1024
#: tiles per pallas_call; larger rounds launch in chunks of this many
MAX_LAUNCH_TILES = 4096


def bank_smem_bytes(capacity: int, s_max: int) -> int:
    """SMEM the four flattened bank arrays take, tiled in (8, 128) words."""
    rows = -(-capacity // 8) * 8
    row_words = -(-s_max * IM_DEPTH // 128) * 128
    return 4 * rows * row_words * 4


def max_bank_capacity(s_max: int) -> int:
    """Most contexts whose bank fits SMEM beside a full launch's tile ids."""
    budget = SMEM_BYTES - SMEM_RESERVED_BYTES - 4 * MAX_LAUNCH_TILES
    return budget // bank_smem_bytes(8, s_max) * 8


#: largest bank the kernel serves at the default ``S_MAX``: 120 contexts.
#: ``tests/test_chip_compile.py`` compiles it for v5e, and checks that one
#: more context overflows SMEM at a full ``MAX_LAUNCH_TILES`` launch.
MAX_BANK_CAPACITY = max_bank_capacity(S_MAX)


def check_dtype(dtype) -> None:
    """Refuse a dtype Mosaic cannot serve: the kernel slices one row of the
    register file at a dynamic offset, which a packed (16-bit) dtype
    cannot do."""
    if jnp.dtype(dtype).itemsize != 4:
        raise ValueError(
            f"the Pallas TMFU kernel serves 32-bit dtypes only, not "
            f"{jnp.dtype(dtype).name}: Mosaic cannot load one row of a "
            f"packed register file at a dynamic offset; use backend='jnp'")


def check_bank(capacity: int, s_max: int) -> None:
    """Refuse, before anything is compiled, a bank SMEM cannot hold."""
    fit = max_bank_capacity(s_max)
    if capacity > fit:
        raise ValueError(
            f"a bank of {capacity} contexts (s_max={s_max}) does not fit "
            f"the Pallas TMFU kernel's SMEM ({SMEM_BYTES} B, less "
            f"{SMEM_RESERVED_BYTES} B reserve and {MAX_LAUNCH_TILES} tile "
            f"ids): at most {fit} contexts; use backend='jnp' for more")


def _branch_table(dtype):
    """Opcode-indexed branch table; operands are (1, bt) vectors."""
    def _bitwise(fn):
        def g(va, vb, cv):
            if jnp.issubdtype(dtype, jnp.floating):
                it = jnp.int32 if dtype.itemsize == 4 else jnp.int16
                ia = jax.lax.bitcast_convert_type(va, it)
                ib = jax.lax.bitcast_convert_type(vb, it)
                return jax.lax.bitcast_convert_type(fn(ia, ib), dtype)
            return fn(va, vb)
        return g

    return [
        lambda va, vb, cv: va,                     # BYP
        lambda va, vb, cv: va + vb,                # ADD
        lambda va, vb, cv: va - vb,                # SUB
        lambda va, vb, cv: va * vb,                # MUL
        lambda va, vb, cv: va + cv,                # ADDC
        lambda va, vb, cv: va - cv,                # SUBC
        lambda va, vb, cv: cv - va,                # RSUBC
        lambda va, vb, cv: va * cv,                # MULC
        lambda va, vb, cv: va * va,                # SQR
        lambda va, vb, cv: jnp.maximum(va, vb),    # MAX
        lambda va, vb, cv: jnp.minimum(va, vb),    # MIN
        lambda va, vb, cv: jnp.abs(va),            # ABS
        lambda va, vb, cv: -va,                    # NEG
        _bitwise(jnp.bitwise_and),                 # AND
        _bitwise(jnp.bitwise_or),                  # OR
        _bitwise(jnp.bitwise_xor),                 # XOR
        lambda va, vb, cv: va,                     # OUT
        lambda va, vb, cv: jnp.zeros_like(va),     # NOP
    ]


def _run_stages(word, op_ref, a_ref, b_ref, imm_ref, rf_a, rf_b,
                *, n_stages: int, dtype):
    """Shared stage loop: run ``n_stages`` over the ping-pong RF buffers.

    ``word(s, i)`` is the SMEM index of stage ``s``'s instruction ``i`` —
    ``(s, i)`` in the single-context [S, IM] layout, ``(cid, s * IM + i)``
    in the flattened multi-tenant [N, S * IM] bank — so the two datapaths
    cannot drift apart.

    Stages run in pairs (rf_a -> rf_b, then rf_b -> rf_a), so each
    instruction's source and destination buffers are static.  The opcode
    dispatch is one flat ``pl.when`` per branch-table entry: exactly one
    fires, so one branch executes, and Mosaic sees no nested region (an
    18-way ``lax.switch`` nests one region per branch, which overflows the
    TPU compiler's layout inference).
    """
    branches = _branch_table(dtype)
    is_float = jnp.issubdtype(dtype, jnp.floating)
    bt = rf_a.shape[-1]

    def run_stage(s, src, dst):
        def instr_body(i, carry):
            at = word(s, i)
            va = src[pl.ds(a_ref[at], 1), :]
            vb = src[pl.ds(b_ref[at], 1), :]
            # the immediate is an SMEM scalar; Mosaic bitcasts vectors only
            raw = jnp.broadcast_to(imm_ref[at], (1, bt))
            if is_float:
                cv = jax.lax.bitcast_convert_type(
                    raw, jnp.float32).astype(dtype)
            else:
                cv = raw.astype(dtype)
            opc = op_ref[at]
            for code, fn in enumerate(branches):
                @pl.when(opc == code)
                def _(fn=fn):
                    dst[pl.ds(i, 1), :] = fn(va, vb, cv)
            return carry

        jax.lax.fori_loop(0, IM_DEPTH, instr_body, 0)

    def stage_pair(p, carry):
        run_stage(2 * p, rf_a, rf_b)
        run_stage(2 * p + 1, rf_b, rf_a)
        return carry

    jax.lax.fori_loop(0, n_stages // 2, stage_pair, 0)
    if n_stages % 2:
        run_stage(n_stages - 1, rf_a, rf_b)


def _tmfu_kernel(op_ref, a_ref, b_ref, imm_ref,   # scalar-prefetch (SMEM)
                 x_ref, o_ref,                    # VMEM in/out tiles
                 rf_a, rf_b,                      # VMEM scratch (ping-pong)
                 *, n_stages: int, dtype):
    rf_a[...] = x_ref[...]
    _run_stages(lambda s, i: (s, i), op_ref, a_ref, b_ref, imm_ref,
                rf_a, rf_b, n_stages=n_stages, dtype=dtype)
    # after S stages the live RF is rf_a if S even else rf_b
    if n_stages % 2 == 0:
        o_ref[...] = rf_a[...]
    else:
        o_ref[...] = rf_b[...]


def _tmfu_kernel_multi(ids_ref, op_ref, a_ref, b_ref, imm_ref,  # SMEM
                       x_ref, o_ref,                    # VMEM in/out tiles
                       rf_a, rf_b,                      # VMEM scratch
                       *, n_stages: int, dtype):
    """Multi-tenant TMFU: grid step g executes context ``ids_ref[g]``.

    The instruction bank rides in SMEM as flattened [N, S * IM] arrays; the
    per-tile context id is a scalar-prefetch operand, so selecting a kernel
    is an SMEM row offset — the serving analogue of pointing the FU at a
    different daisy-chained context, with zero recompilation.
    """
    cid = ids_ref[pl.program_id(0)]
    rf_a[...] = x_ref[0]
    _run_stages(lambda s, i: (cid, s * IM_DEPTH + i), op_ref, a_ref, b_ref,
                imm_ref, rf_a, rf_b, n_stages=n_stages, dtype=dtype)
    if n_stages % 2 == 0:
        o_ref[...] = rf_a[...][None]
    else:
        o_ref[...] = rf_b[...][None]


def _tmfu_rf_multi(op, src_a, src_b, imm_i32, ctx_ids, x,
                   interpret: bool, alias_x: bool):
    """Shared pallas_call builder for the multi-tenant RF pipeline.

    ``alias_x`` maps operand 5 (the [G, RF_DEPTH, T] tile stack — same
    shape/dtype as the output) onto output 0 via ``input_output_aliases``,
    so the donated input allocation IS the result buffer.  Operand indices
    count the scalar-prefetch operands: (ctx_ids, op, src_a, src_b, imm) =
    0..4, x = 5.
    """
    n_bank, n_stages, im = op.shape
    n_tiles, rf_depth, tile = x.shape
    assert rf_depth == RF_DEPTH and im == IM_DEPTH
    assert ctx_ids.shape == (n_tiles,)
    dtype = x.dtype

    kernel = functools.partial(_tmfu_kernel_multi, n_stages=n_stages,
                               dtype=dtype)
    # [N, S * IM] rows fill whole SMEM tiles (see the SMEM budget above)
    flat = [w.reshape(n_bank, n_stages * im)
            for w in (op, src_a, src_b, imm_i32)]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((1, RF_DEPTH, tile),
                                   lambda g, *_: (g, 0, 0))],
            out_specs=pl.BlockSpec((1, RF_DEPTH, tile),
                                   lambda g, *_: (g, 0, 0)),
            scratch_shapes=[pltpu.VMEM((RF_DEPTH, tile), dtype),
                            pltpu.VMEM((RF_DEPTH, tile), dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles, RF_DEPTH, tile), dtype),
        input_output_aliases={5: 0} if alias_x else {},
        interpret=interpret,
    )(ctx_ids, *flat, x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tmfu_pipeline_rf_multi(op, src_a, src_b, imm_i32, ctx_ids, x,
                           *, interpret: bool):
    """Run a mixed-context tile batch: x [G, RF_DEPTH, T] -> [G, RF_DEPTH, T].

    op/src_a/src_b/imm_i32: stacked bank arrays [N, S, IM] int32;
    ctx_ids: [G] int32 selecting the context for each batch tile.  One
    pallas_call, one executable, any mix of resident kernels.
    """
    return _tmfu_rf_multi(op, src_a, src_b, imm_i32, ctx_ids, x,
                          interpret=interpret, alias_x=False)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(5,))
def tmfu_pipeline_rf_multi_donated(op, src_a, src_b, imm_i32, ctx_ids, x,
                                   *, interpret: bool):
    """``tmfu_pipeline_rf_multi`` with the tile stack donated AND aliased.

    The [G, RF_DEPTH, T] input has exactly the output's shape and dtype,
    so ``input_output_aliases`` lets the round's staging allocation be
    reused as its result — zero extra device buffers per round.  Caller
    contract: ``x`` is dead after this call (the serving engines consume
    each batch exactly once; see ``Overlay(donate=True)``).
    """
    return _tmfu_rf_multi(op, src_a, src_b, imm_i32, ctx_ids, x,
                          interpret=interpret, alias_x=True)


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def tmfu_pipeline_rf(op, src_a, src_b, imm_i32, x,
                     block_batch: int = DEFAULT_BLOCK_BATCH,
                     *, interpret: bool):
    """Run the overlay pipeline: x [RF_DEPTH, B] -> final RF [RF_DEPTH, B].

    op/src_a/src_b: [S, IM_DEPTH] int32; imm_i32: int32 bit-patterns of the
    f32 immediates (or raw ints for integer datapaths).  B must be a
    multiple of ``block_batch``.
    """
    n_stages, im = op.shape
    rf_depth, batch = x.shape
    assert rf_depth == RF_DEPTH and im == IM_DEPTH
    assert batch % block_batch == 0, (batch, block_batch)
    dtype = x.dtype

    grid = (batch // block_batch,)
    kernel = functools.partial(_tmfu_kernel, n_stages=n_stages, dtype=dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[pl.BlockSpec((RF_DEPTH, block_batch),
                                   lambda t, *_: (0, t))],
            out_specs=pl.BlockSpec((RF_DEPTH, block_batch),
                                   lambda t, *_: (0, t)),
            scratch_shapes=[pltpu.VMEM((RF_DEPTH, block_batch), dtype),
                            pltpu.VMEM((RF_DEPTH, block_batch), dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((RF_DEPTH, batch), dtype),
        interpret=interpret,
    )(op, src_a, src_b, imm_i32, x)
