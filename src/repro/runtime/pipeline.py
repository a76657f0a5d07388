"""Linear time-multiplexed stage pipeline over devices (paper Fig. 2 at
cluster scale).

The overlay's architecture maps 1:1 onto pipeline parallelism:

  FPGA overlay                      this runtime
  ------------------------------    ----------------------------------------
  linear array of S TM-FUs          S pipeline stages on a 1-D mesh axis
  FU executes its stage's ops       stage executes its slice of layers
  direct FU->FU link (no routing)   lax.ppermute to the next neighbour only
  data packets streaming in         M microbatches streaming in
  II = bottleneck-stage cycles      II = M + S - 1 slots for M outputs
  pipeline replication (Fig. 4)     data-parallel axis around the pipeline

``pipeline_apply`` runs inside shard_map on the 'stage' axis: each device
holds ONE stage's parameters (the FU's instruction memory analogue) and the
schedule is the paper's Table I generalized: slot t runs microbatch
t - stage on stage ``stage``.

Overlap: the ppermute of slot t's activations is issued in the same slot
as the next stage compute, so on real hardware the neighbour transfer
hides behind the stage's layer compute (compute/comm overlap).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_ii(n_microbatches: int, n_stages: int) -> dict:
    """The paper's II model generalized to the device pipeline."""
    slots = n_microbatches + n_stages - 1
    return {
        "slots": slots,
        "bubble_fraction": (n_stages - 1) / slots,
        "ii_per_output": slots / n_microbatches,
    }


def _stage_slice(tree, idx):
    return jax.tree.map(lambda x: x[idx], tree)


def pipeline_apply(mesh: Mesh, stage_fn, stage_params, x, *,
                   axis: str = "stage", collect_dtype=None):
    """Run x through S chained stages with microbatch streaming.

    stage_fn(params_i, h) -> h  (one stage's compute, e.g. its layer slice)
    stage_params: pytree with leading dim S (stage-sharded)
    x: [M, mb, ...] microbatches (replicated across the stage axis)

    Returns y [M, mb, ...] — outputs of the final stage, microbatch order.
    """
    S = mesh.shape[axis]
    M = x.shape[0]

    def worker(params_local, xs):
        # params_local: leaves [1, ...]; xs: [M, mb, ...] (replicated)
        params_i = _stage_slice(params_local, 0)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype if collect_dtype is None
                          else collect_dtype)
        outputs = jnp.zeros_like(xs)

        def slot(t, carry):
            state, outputs = carry
            # stage 0 injects microbatch t; others consume neighbour data
            inj = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            h_in = jnp.where(stage == 0, inj, state)
            h_out = stage_fn(params_i, h_in)
            # the last stage records output for microbatch t-(S-1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            take = jnp.logical_and(stage == S - 1, t >= S - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(take,
                          h_out.astype(outputs.dtype),
                          jax.lax.dynamic_index_in_dim(
                              outputs, out_idx, 0, keepdims=False)),
                out_idx, 0)
            # direct neighbour link (the non-programmable interconnect)
            state = jax.lax.ppermute(
                h_out, axis, [(i, (i + 1) % S) for i in range(S)])
            return state, outputs

        state, outputs = jax.lax.fori_loop(0, M + S - 1, slot,
                                           (state, outputs))
        # only the last stage holds real outputs; broadcast them
        outputs = jnp.where(stage == S - 1, outputs, 0)
        return jax.lax.psum(outputs, axis)

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params), P())
    return jax.shard_map(worker, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(stage_params, x)


def pipeline_reference(stage_fn, stage_params, x):
    """Sequential oracle: all stages applied in order to each microbatch."""
    S = jax.tree.leaves(stage_params)[0].shape[0]

    def one(mb):
        h = mb
        for i in range(S):
            h = stage_fn(_stage_slice(stage_params, i), h)
        return h

    return jax.vmap(one)(x)
