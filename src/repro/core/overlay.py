"""The overlay object: compile-once executor + fast context switching.

Public API::

    ov = Overlay(s_max=16)                      # 'configure the FPGA' once
    ctx = ov.load(compile_program(dfg))         # context switch (no recompile)
    ys = ov(ctx, xs)                            # stream a batch through

``Overlay.load`` is the paper's 0.27 µs daisy-chain analogue: only int32
instruction words + constant tables move; the XLA executable is untouched.
``spatial_jit`` is the SCFU-SCN / vendor-flow analogue: the DFG is inlined
into a fresh XLA program (1 HLO op per DFG node) and must be recompiled per
kernel.  benchmarks/context_switch.py and benchmarks/area_analogue.py
measure the two against each other.

Multi-tenant dispatch is a STAGED PIPELINE so a serving engine can overlap
the host-side work of one round with the device execution of another::

    plan     = ov.plan(bank, requests)     # residency + tile layout (host)
    batch    = ov.assemble(plan)           # one [G,RF,tile] host buffer
    ys       = ov.execute(bank, batch)     # async device launch, NO block
    outs     = ov.collect(plan, ys)        # slice per request (lazy)

``Overlay.dispatch`` is exactly ``collect(execute(assemble(plan)))`` — the
synchronous composition is the bit-for-bit oracle for the async engine in
``launch.serve.OverlayServer``, which interleaves the stages of successive
rounds.  ``plan(..., pin=True)`` pins every referenced context in the bank
until ``plan.release(bank)``, so a later round's planning can never evict
a context out from under an in-flight launch (see ``core.bank``).
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import vm
from repro.core.arena import ArenaBlock, RoundArena
from repro.core.bank import (DEFAULT_MAX_OUTPUTS, BankError, ContextBank,
                             context_key)
from repro.core.dfg import DFG
from repro.core.isa import RF_DEPTH, Program, encode
from repro.core.schedule import Schedule, schedule
from repro.core.vm import Context, dfg_eval, make_context, pad_inputs

#: default per-tile batch width for bank dispatch (VPU lane multiple)
DISPATCH_TILE = 128


@dataclasses.dataclass
class CompiledKernel:
    dfg: DFG
    sched: Schedule
    program: Program


@dataclasses.dataclass
class _GroupSpec:
    """Tile layout of one kernel group inside a dispatch round."""

    key: tuple                # context identity (bank.context_key)
    idxs: list                # request indices, submission order
    kernel: CompiledKernel
    slot: int                 # bank slot the group's tiles select
    lens: list                # per-request batch lengths
    total: int                # sum(lens)
    n_tiles: int              # ceil(total / tile)
    start: int                # first row of this group in the tile stack


@dataclasses.dataclass
class DispatchPlan:
    """Host-side layout of one mixed-kernel round (output of ``plan``).

    Carries everything ``assemble``/``collect`` need to build the tile
    stack and slice results back out, plus the request list itself so the
    stages cannot be fed mismatched arguments.  When built with
    ``pin=True`` the referenced contexts are pinned in the bank; call
    ``release(bank)`` exactly once after ``collect`` (or on abandon).
    """

    tile: int
    requests: list            # the [(CompiledKernel, xs)] pairs, verbatim
    groups: list              # [_GroupSpec]
    g_total: int              # live tile rows
    g_pad: int                # pow2-padded tile rows (executable bucket)
    pinned: bool = False
    arena: RoundArena | None = None   # pool the staging block came from
    block: ArenaBlock | None = None   # host block owned until release()

    @property
    def n_kernels(self) -> int:
        return len(self.groups)

    def release(self, bank: ContextBank) -> None:
        """Drop this plan's eviction pins and recycle its arena block.

        Called exactly once per round after delivery (``collect``); a
        no-op for unpinned, arena-less plans.  The host staging block is
        safe to reuse here because ``execute``'s device placement COPIES
        it — the launch never aliases host memory.
        """
        if self.pinned:
            self.pinned = False
            for g in self.groups:
                bank.unpin(g.kernel)
        if self.arena is not None:
            self.arena.recycle(self.block)
            self.arena = None
            self.block = None


@partial(jax.jit, static_argnames=("n_tiles", "n_out"))
def _gather_live(ys, n_tiles: int, n_out: int):
    """Device-side live-rows slice + transpose for ``collect(host=True)``.

    Drops the padding tiles and the dead ``max_outputs`` rows BEFORE the
    host transfer, and moves the output axis outermost so each group's
    per-output flatten on the host is a contiguous view, not a copy.
    ``n_tiles`` is bucketed by the caller (multiple of 8, capped at
    ``g_pad``) so steady traffic reuses a handful of executables.
    """
    return jnp.moveaxis(ys[:n_tiles, :n_out, :], 1, 0)


def _round_up8(n: int) -> int:
    return -(-n // 8) * 8


def _on_device(arr, device) -> bool:
    """True when ``arr`` is a jax.Array already resident on ``device``."""
    sharding = getattr(arr, "sharding", None)
    return (sharding is not None
            and getattr(sharding, "device_set", None) == {device})


def _host_backed(arr) -> bool:
    """True when ``arr``'s buffer lives in host memory (numpy, or a
    jax.Array on CPU devices) — i.e. ``np.asarray`` on it is zero-copy
    and a device-side gather would only add dispatch latency."""
    if isinstance(arr, np.ndarray):
        return True
    sharding = getattr(arr, "sharding", None)
    if sharding is None:
        return False
    return all(d.platform == "cpu" for d in sharding.device_set)


def compile_program(dfg: DFG) -> CompiledKernel:
    """Full mapping flow: DFG -> schedule -> encoded context image."""
    sched = schedule(dfg)
    program = encode(sched)
    # record the RF slots of the primary outputs in the final stage stream
    final = sched.stages[-1]
    slot_of = {ins.dest: i for i, ins in enumerate(final.instrs)}
    program._output_slots = np.asarray(
        [slot_of[o] for o in dfg.outputs], dtype=np.int32)
    return CompiledKernel(dfg=dfg, sched=sched, program=program)


class Overlay:
    """A fixed executor for a family of kernels (<= s_max stages)."""

    def __init__(self, s_max: int = vm.S_MAX, dtype=jnp.float32,
                 backend: str = "jnp", device=None,
                 arena: RoundArena | None = None, donate: bool = False):
        if backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        #: what executes the rounds: ``jnp`` (the VM), ``pallas-mosaic``
        #: (the kernel compiled for a TPU) or ``pallas-interpret`` (the
        #: Pallas interpreter, CPU devices only); fixed by the platform of
        #: ``device`` (else the default device), never chosen silently
        self.executor = "jnp"
        if backend == "pallas":
            from repro.kernels.tmfu.kernel import check_dtype
            from repro.kernels.tmfu.ops import interpret_for
            check_dtype(dtype)
            self.executor = ("pallas-interpret" if interpret_for(device)
                             else "pallas-mosaic")
        self.s_max = s_max
        self.dtype = dtype
        self.backend = backend
        #: device this overlay's contexts and launches are pinned to;
        #: None = JAX default.  A sharded serving replica pins its overlay
        #: (and its ContextBank) so rounds execute where the working set
        #: is resident, never via implicit default-device placement.
        self.device = device
        #: host staging pool for ``assemble``; None = allocate per round.
        #: The caller owns the recycle protocol: every assembled plan must
        #: eventually see ``plan.release(bank)`` (the serving engines do).
        self.arena = arena
        #: donate the round's device tile stack to the executor so XLA
        #: frees/reuses the input allocation instead of holding it until
        #: the round retires.  Contract: the caller must not touch the
        #: batch after ``execute`` consumes it (the engines never do).
        self.donate = donate
        #: reusable packing scratch for ``assemble`` (grown on demand);
        #: per-overlay, so concurrent engines never share it.
        self._scratch: np.ndarray | None = None

    # --------------------------------------------------------------- context
    def load(self, kernel: CompiledKernel) -> Context:
        """Context switch: build + device_put the instruction image.

        The arrays are placed field by field: ``Context`` is a plain
        dataclass, not a registered pytree, so a ``jax.tree.map`` over it
        would treat the whole context as one leaf and silently skip the
        transfer — the image would stay on the default device no matter
        what this overlay is pinned to (regression-tested in
        tests/test_sharded_serving.py).
        """
        ctx = make_context(kernel.program, self.s_max, self.dtype)
        return dataclasses.replace(
            ctx, **{f: jax.device_put(getattr(ctx, f), self.device)
                    for f in ("op", "src_a", "src_b", "imm", "out_idx")})

    # --------------------------------------------------------------- execute
    def __call__(self, ctx: Context, xs: list[jax.Array]) -> list[jax.Array]:
        x = pad_inputs([jnp.asarray(v, self.dtype) for v in xs],
                       device=self.device)
        if self.backend == "pallas":
            from repro.kernels.tmfu import ops as tmfu_ops
            ys = tmfu_ops.tmfu_pipeline(ctx, x, device=self.device)
        else:
            ys = vm.vm_exec(ctx.tree(), ctx.out_idx, x)
        return [ys[i] for i in range(ctx.n_outputs)]

    # ---------------------------------------------------------- multi-tenant
    def load_many(self, kernels, capacity: int | None = None,
                  max_outputs: int = DEFAULT_MAX_OUTPUTS) -> ContextBank:
        """Load a family of kernels into a fresh ContextBank.

        The bank's stacked arrays feed ``vm_exec_multi`` (or the Pallas
        multi kernel) so every resident kernel is reachable by slot id from
        ONE compiled executable.  A bank the Pallas kernel cannot hold in
        SMEM is refused here, before anything compiles.
        """
        ks = list(kernels)
        capacity = capacity or max(len(ks), 1)
        if self.backend == "pallas":
            from repro.kernels.tmfu.kernel import check_bank
            check_bank(capacity, self.s_max)
        bank = ContextBank(capacity, s_max=self.s_max,
                           dtype=self.dtype, max_outputs=max_outputs,
                           device=self.device)
        for k in ks:
            bank.load(k)
        return bank

    def plan(self, bank: ContextBank, requests, tile: int = DISPATCH_TILE,
             pin: bool = False) -> DispatchPlan:
        """Stage 1/4 — residency + tile layout for a mixed-kernel round.

        ``requests`` is a list of ``(CompiledKernel, xs)`` pairs (``xs`` a
        list of 1-D input arrays, all the same length within a request).
        Requests are grouped by context CONTENT (not name: two distinct
        programs sharing a name must never be served from one slot), every
        group's kernel is made bank-resident (this is the prefetch point —
        the device context writes overlap whatever is already executing),
        and each group gets a run of fixed-width tile rows.  The round may
        reference at most ``bank.capacity`` distinct kernels; larger
        working sets are split into rounds by ``launch.serve``.

        With ``pin=True`` each referenced context is refcount-pinned until
        ``DispatchPlan.release(bank)`` — required whenever another round
        may load contexts between this plan and its ``execute``.
        """
        groups: OrderedDict[tuple, list[int]] = OrderedDict()
        for i, (k, _) in enumerate(requests):
            groups.setdefault(context_key(k.program), []).append(i)
        if len(groups) > bank.capacity:
            raise BankError(
                f"batch references {len(groups)} kernels > bank capacity "
                f"{bank.capacity}; split into rounds (see OverlayServer)")

        specs: list[_GroupSpec] = []
        g_total = 0
        try:
            for key, idxs in groups.items():
                kern = requests[idxs[0]][0]
                slot = bank.pin(kern) if pin else bank.load(kern)
                lens = [int(np.shape(requests[i][1][0])[0]) for i in idxs]
                total = sum(lens)
                n_tiles = -(-total // tile)
                specs.append(_GroupSpec(key=key, idxs=idxs, kernel=kern,
                                        slot=slot, lens=lens, total=total,
                                        n_tiles=n_tiles, start=g_total))
                g_total += n_tiles
        except BankError:
            # unwind pins already taken by this (never-returned) plan — a
            # caller can't release() a plan it never got
            if pin:
                for g in specs:
                    bank.unpin(g.kernel)
            raise
        g_pad = 1 << (g_total - 1).bit_length() if g_total else 0
        return DispatchPlan(tile=tile, requests=list(requests), groups=specs,
                            g_total=g_total, g_pad=g_pad, pinned=pin)

    def assemble(self, plan: DispatchPlan):
        """Stage 2/4 — build the round's host tile stack (single pass).

        Packs every request into ONE ``[G_pad, RF_DEPTH, tile]`` host
        buffer (a single device transfer — the hot serving path must not
        pay per-group/per-tile dispatches) plus the per-tile context-id
        vector.  The tile count is padded to the next power of two with
        replicas of tile 0 so repeated mixed workloads land in a handful
        of executable buckets (zero retraces after warmup).

        Each group's rows are concatenated ONCE into a pooled overlay
        scratch (``np.concatenate(..., out=)`` — no intermediate
        allocation) and stored with a single strided scatter into the
        group's tile run — the legacy per-group ``np.zeros`` + concat
        copy + ``reshape().transpose()`` triple pass survives only as
        ``assemble_reference``, the paired-benchmark baseline.  With
        ``self.arena`` set the destination is a recycled pool block
        (scrubbed to its dirty high-water mark, so contents are
        bit-identical to a fresh zeros) that ``plan.release(bank)``
        returns to the pool.

        Pure host work (numpy) plus an async device placement: in the
        async engine this stage runs for round N+1 while round N executes
        on device.  Returns ``(id_arr, x_stack)`` — already resident on
        ``self.device`` when one is pinned, so ``execute`` skips its
        placement — or ``None`` when the round is all zero-length
        requests (nothing to launch).
        """
        if plan.g_total == 0:
            return None
        np_dtype = np.dtype(self.dtype)
        tile = plan.tile
        if self.arena is not None:
            if plan.block is not None:       # re-assembled plan: no leak
                plan.arena.recycle(plan.block)
            block = self.arena.checkout(plan.g_pad, tile, np_dtype)
            plan.arena, plan.block = self.arena, block
            x_np, ids_np = block.x, block.ids
        else:
            block = None
            x_np = np.zeros((plan.g_pad, RF_DEPTH, tile), np_dtype)
            ids_np = np.zeros(plan.g_pad, np.int32)
        max_cols = max((g.n_tiles for g in plan.groups), default=0) * tile
        scratch = self._scratch
        if (scratch is None or scratch.dtype != np_dtype
                or scratch.shape[1] < max_cols):
            scratch = self._scratch = np.empty((RF_DEPTH, max_cols), np_dtype)
        dirty = 0
        for g in plan.groups:
            if g.n_tiles == 0:
                continue
            n_in = len(g.kernel.dfg.inputs)
            dirty = max(dirty, n_in)
            nt = g.n_tiles
            buf = scratch[:n_in, :nt * tile]    # [n_in, nt*tile] pooled
            for j in range(n_in):
                np.concatenate([np.asarray(plan.requests[i][1][j], np_dtype)
                                for i in g.idxs], out=buf[j, :g.total])
            if g.total < nt * tile:
                buf[:, g.total:] = 0            # zero tail of the last tile
            # single strided store: row j of the scratch lands in RF row j
            # of every tile in the group's run
            x_np[g.start:g.start + nt, :n_in, :] = \
                buf.reshape(n_in, nt, tile).transpose(1, 0, 2)
            ids_np[g.start:g.start + g.n_tiles] = g.slot
        # padding tiles replicate tile 0; only its dirty rows can be
        # nonzero, so copying those rows is bit-identical to a full copy
        if plan.g_total < plan.g_pad:
            x_np[plan.g_total:, :dirty] = x_np[0, :dirty]
        ids_np[plan.g_total:] = ids_np[0]
        if block is not None:
            block.dirty_rows = max(block.dirty_rows, dirty)
        if self.device is not None:
            return jax.device_put((ids_np, x_np), self.device)
        return jnp.asarray(ids_np), jnp.asarray(x_np)

    def assemble_reference(self, plan: DispatchPlan):
        """The seed's copy-heavy assemble, kept verbatim as the paired
        baseline for ``benchmarks/hot_path.py`` and the bit-parity tests
        (``assemble`` must reproduce this buffer exactly)."""
        if plan.g_total == 0:
            return None
        np_dtype = np.dtype(self.dtype)
        tile = plan.tile
        x_np = np.zeros((plan.g_pad, RF_DEPTH, tile), np_dtype)
        ids_np = np.zeros(plan.g_pad, np.int32)
        for g in plan.groups:
            if g.n_tiles == 0:
                continue
            n_in = len(g.kernel.dfg.inputs)
            buf = np.zeros((n_in, g.n_tiles * tile), np_dtype)
            for j in range(n_in):
                buf[j, :g.total] = np.concatenate(
                    [np.asarray(plan.requests[i][1][j], np_dtype)
                     for i in g.idxs])
            x_np[g.start:g.start + g.n_tiles, :n_in, :] = \
                buf.reshape(n_in, g.n_tiles, tile).transpose(1, 0, 2)
            ids_np[g.start:g.start + g.n_tiles] = g.slot
        x_np[plan.g_total:] = x_np[0]
        ids_np[plan.g_total:] = ids_np[0]
        return jnp.asarray(ids_np), jnp.asarray(x_np)

    def execute(self, bank: ContextBank, batch):
        """Stage 3/4 — launch the round on device; does NOT block.

        Snapshots the bank's stacked instruction arrays at call time (the
        arrays are immutable — later ``bank.load`` writes produce NEW
        arrays, so an in-flight launch is never disturbed) and issues one
        ``vm_exec_multi`` / ``tmfu_pipeline_multi`` call.  JAX dispatch is
        asynchronous: the returned ``[G_pad, max_outputs, tile]`` array is
        a future; only ``jax.block_until_ready`` (the engine's delivery
        point) waits on it.  Slot validity between ``plan`` and this call
        is the caller's contract — hold plan pins if any other round may
        touch the bank in between.
        """
        if batch is None:
            return None
        id_arr, x_stack = batch
        # co-locate the round with the bank: a device-pinned bank (sharded
        # replica) must execute where its contexts are resident — mixing a
        # committed bank with default-device inputs is an XLA placement
        # error, not a transfer.  ``assemble`` already places on
        # ``self.device``, so the placement here only fires for batches
        # built elsewhere — never a redundant no-op put per round.
        device = getattr(bank, "device", None) or self.device
        if device is not None and not (_on_device(id_arr, device)
                                       and _on_device(x_stack, device)):
            id_arr, x_stack = jax.device_put((id_arr, x_stack), device)
        if self.backend == "pallas":
            from repro.kernels.tmfu import ops as tmfu_ops
            return tmfu_ops.tmfu_pipeline_multi(bank, id_arr, x_stack,
                                                donate=self.donate)
        if self.donate:
            # XLA frees (rather than aliases) the donation here: the jnp
            # executor's [G, max_outputs, tile] result is narrower than
            # the donated stack, and its lowering warns about the partial
            # use at every compile — expected, so keep each bucket quiet
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                return vm.vm_exec_multi_donated(bank.tree(), bank.out_idx,
                                                id_arr, x_stack)
        return vm.vm_exec_multi(bank.tree(), bank.out_idx, id_arr, x_stack)

    def collect(self, plan: DispatchPlan, ys, host: bool = False):
        """Stage 4/4 — slice the round's result stack back per request.

        Two delivery modes:

        * ``host=False`` (the ``dispatch`` default): the slices are lazy
          device ops on the (possibly still executing) result array —
          nothing blocks, results stay ``jax.Array``.
        * ``host=True`` (the streaming engine's delivery path): ``ys``
          must already be ready (the engine just blocked on it); only
          the LIVE ``g_total`` tiles and live output rows reach a
          contiguous host buffer, in ONE bulk gather; every per-group
          flatten and per-request slice after that is a numpy VIEW —
          the padding tiles and dead ``max_outputs`` rows are never
          copied.  On an accelerator the slice+transpose runs device-
          side (``_gather_live`` — one fused op, tile count bucketed to
          a multiple of 8 so steady traffic never retraces) so the one
          host transfer carries live bytes only; for a host-backed
          result (CPU jax) ``np.asarray`` is already zero-copy, so the
          gather is a single strided ``np.copyto`` of the live view —
          no XLA dispatch on the delivery path at all.

        Returns one output list per request, in request order; both modes
        yield bit-identical values.  ``collect_reference`` keeps the
        seed's full-stack readback as the paired-benchmark baseline.
        """
        if ys is None:
            return [[jnp.zeros((0,), self.dtype) for _ in k.dfg.outputs]
                    for k, _ in plan.requests]
        if host:
            if _host_backed(ys):
                arr = None
                view = np.asarray(ys)            # zero-copy on CPU
            else:
                n_live = max((len(g.kernel.dfg.outputs)
                              for g in plan.groups), default=1)
                nt = min(plan.g_pad, _round_up8(plan.g_total))
                arr = np.asarray(_gather_live(ys, nt, max(n_live, 1)))
        results: list = [None] * len(plan.requests)
        for g in plan.groups:
            n_out = len(g.kernel.dfg.outputs)
            if host:
                if arr is None:
                    # one strided gather per group: exactly this group's
                    # live output rows, output axis out front so each row
                    # flattens to a contiguous view
                    buf = view[g.start:g.start + g.n_tiles, :n_out, :] \
                        .transpose(1, 0, 2).copy()
                    flats = [buf[j].reshape(-1) for j in range(n_out)]
                else:
                    # [n_live, nt, tile] device-gathered stack: per-group
                    # flattens are contiguous views of the one transfer
                    flats = [arr[j, g.start:g.start + g.n_tiles].reshape(-1)
                             for j in range(n_out)]
            else:
                block = ys[g.start:g.start + g.n_tiles]  # [nt, max_out, tile]
                flat = jnp.moveaxis(block, 1, 0).reshape(ys.shape[1], -1)
                flats = [flat[j] for j in range(n_out)]
            off = 0
            for i, n in zip(g.idxs, g.lens):
                results[i] = [flats[j][off:off + n] for j in range(n_out)]
                off += n
        return results

    def collect_reference(self, plan: DispatchPlan, ys, host: bool = False):
        """The seed's collect: full padded-stack readback + one
        ``ascontiguousarray`` copy per live output row.  Paired-benchmark
        baseline; bit-identical to ``collect`` in both modes."""
        if ys is None:
            return [[jnp.zeros((0,), self.dtype) for _ in k.dfg.outputs]
                    for k, _ in plan.requests]
        if host:
            ys = np.asarray(ys)
        results: list = [None] * len(plan.requests)
        for g in plan.groups:
            n_out = len(g.kernel.dfg.outputs)
            block = ys[g.start:g.start + g.n_tiles]    # [nt, max_out, tile]
            if host:
                flats = [np.ascontiguousarray(block[:, j, :]).reshape(-1)
                         for j in range(n_out)]
            else:
                flat = jnp.moveaxis(block, 1, 0).reshape(ys.shape[1], -1)
                flats = [flat[j] for j in range(n_out)]
            off = 0
            for i, n in zip(g.idxs, g.lens):
                results[i] = [flats[j][off:off + n] for j in range(n_out)]
                off += n
        return results

    def dispatch(self, bank: ContextBank, requests, tile: int = DISPATCH_TILE):
        """Serve a mixed-kernel batch through the bank in one launch family.

        The synchronous composition of the four pipeline stages —
        ``collect(execute(assemble(plan(...))))`` — and therefore the
        bit-for-bit oracle for the streaming engine, which runs the same
        stages interleaved across rounds.  Returns one output list per
        request, in request order.
        """
        if not requests:
            return []
        p = self.plan(bank, requests, tile=tile)
        ys = self.execute(bank, self.assemble(p))
        # the lazy collect below never blocks, so there is no engine-style
        # delivery point to recycle at; the device placement in execute
        # already copied the staging block, so hand it back now (release
        # on an unpinned plan only recycles)
        p.release(bank)
        return self.collect(p, ys)

    # ------------------------------------------------------------ timing
    def time_context_switch(self, kernel: CompiledKernel,
                            iters: int = 20) -> float:
        """Median seconds to swap a kernel onto the live overlay."""
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            ctx = self.load(kernel)
            jax.block_until_ready(ctx.op)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))


def spatial_jit(dfg: DFG):
    """SCFU-SCN analogue: the DFG inlined into its own XLA program."""

    @jax.jit
    def run(xs: list[jax.Array]) -> list[jax.Array]:
        env = {name: x for name, x in zip(dfg.inputs, xs)}
        out = dfg_eval(dfg, env)
        return [out[o] for o in dfg.outputs]

    return run


def time_recompile(dfg: DFG, xs, iters: int = 3) -> float:
    """Seconds for the vendor-flow analogue: fresh trace + XLA compile."""
    ts = []
    for _ in range(iters):
        fn = spatial_jit(dfg)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(xs))
        ts.append(time.perf_counter() - t0)
        fn._clear_cache()
    return float(np.median(ts))
