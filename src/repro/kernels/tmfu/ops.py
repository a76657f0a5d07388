"""Public jit'd wrapper for the TMFU pipeline kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.isa import RF_DEPTH
from repro.kernels.tmfu.kernel import (DEFAULT_BLOCK_BATCH, MAX_LAUNCH_TILES,
                                       tmfu_pipeline_rf,
                                       tmfu_pipeline_rf_multi,
                                       tmfu_pipeline_rf_multi_donated)


def interpret_for(device=None) -> bool:
    """Pallas mode for a launch on ``device`` (None = the default device).

    Mosaic compiles the kernel on a TPU; a CPU device (the test suite)
    runs the Pallas interpreter.  Any other platform has no TMFU kernel,
    and is refused rather than silently interpreted.
    """
    if device is None:
        device = jax.config.jax_default_device or jax.devices()[0]
    if device.platform == "tpu":
        return False
    if device.platform == "cpu":
        return True
    raise ValueError(f"the Pallas TMFU kernel runs on tpu (Mosaic) or cpu "
                     f"(interpreter), not on {device.platform!r}")


def _imm_to_i32(imm: jax.Array) -> jax.Array:
    """Pack immediates as int32 context words (bitcast f32 for float paths)."""
    if jnp.issubdtype(imm.dtype, jnp.floating):
        return jax.lax.bitcast_convert_type(
            imm.astype(jnp.float32), jnp.int32)
    return imm.astype(jnp.int32)


def tmfu_pipeline(ctx, x: jax.Array,
                  block_batch: int = DEFAULT_BLOCK_BATCH,
                  interpret: bool | None = None,
                  device=None) -> jax.Array:
    """Execute an overlay Context on the Pallas datapath.

    ctx: repro.core.vm.Context;  x: [RF_DEPTH, batch] input RF image.
    Returns the primary outputs, shape [n_outputs, batch].  ``interpret``
    defaults to what ``device`` (the launch's device) calls for.
    """
    if interpret is None:
        interpret = interpret_for(device)
    rf_depth, batch = x.shape
    assert rf_depth == RF_DEPTH
    bt = min(block_batch, _round_up(batch, 128))
    padded = _round_up(batch, bt)
    if padded != batch:
        x = jnp.pad(x, ((0, 0), (0, padded - batch)))
    rf = tmfu_pipeline_rf(ctx.op, ctx.src_a, ctx.src_b,
                          _imm_to_i32(ctx.imm), x,
                          block_batch=bt, interpret=interpret)
    return rf[ctx.out_idx, :batch]


def tmfu_pipeline_multi(bank, ctx_ids: jax.Array, x: jax.Array,
                        donate: bool = False) -> jax.Array:
    """Execute a mixed-context tile batch on the Pallas datapath.

    bank: repro.core.bank.ContextBank; ctx_ids: [G] int32 slot ids;
    x: [G, RF_DEPTH, tile].  Returns [G, max_outputs, tile] — each tile's
    rows gathered through its selected context's output slots (callers
    slice to the kernel's real n_outputs).

    ``donate=True`` hands ``x`` to the pipeline for in-place reuse
    (``input_output_aliases`` — the RF stack has exactly the input's
    shape); ``x`` is dead afterwards, so only consume-once callers (the
    serving engines) may set it.

    The launch runs where the bank lives (``interpret_for(bank.device)``).
    A round of more than ``MAX_LAUNCH_TILES`` tiles launches in chunks of
    that many, so the tile-id vector the kernel keeps in SMEM stays
    bounded (see ``kernel.MAX_BANK_CAPACITY``).
    """
    interpret = interpret_for(bank.device)
    op, src_a, src_b, imm = bank.tree()
    imm = _imm_to_i32(imm)
    ctx_ids = ctx_ids.astype(jnp.int32)
    rf_fn = tmfu_pipeline_rf_multi_donated if donate else tmfu_pipeline_rf_multi
    n_tiles = x.shape[0]
    if n_tiles <= MAX_LAUNCH_TILES:
        rf = rf_fn(op, src_a, src_b, imm, ctx_ids, x, interpret=interpret)
    else:
        step = MAX_LAUNCH_TILES
        rf = jnp.concatenate([
            rf_fn(op, src_a, src_b, imm, ctx_ids[g:g + step], x[g:g + step],
                  interpret=interpret)
            for g in range(0, n_tiles, step)])
    out_rows = bank.out_idx[ctx_ids]                       # [G, max_out]
    return jnp.take_along_axis(rf, out_rows[:, :, None], axis=1)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m
