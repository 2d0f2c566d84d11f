"""The coroutine engine: declarative decoupled-DMA pipelines for Pallas TPU.

This is the TPU-native realization of CoroAMU's execution model
(DESIGN.md §2). The paper's compiler takes *declared* memory operations and
derives the minimized context and schedule (§III-B/§III-C); here a kernel
declares a `CoroSpec` and the builder derives everything else.
Correspondence:

  aload         -> LoadStream            (decoupled HBM->VMEM copy group)
  astore        -> StoreStream           (decoupled VMEM->HBM write-back,
                                          drain-before-reuse + epilogue drain)
  aset n        -> stream group=n        (n copies signalling one slot
                                          semaphore; one wait-group)
  context       -> CoroSpec.vars         (core.context.VarSpec; scratch shape
                                          derived from classify(): private x
                                          depth, shared/sequential x 1)
  getfin/bafin  -> semaphore wait on the slot being resumed (poll/jump)
  SPM slots     -> VMEM scratch shaped (depth, *tile), allocated here
  coroutine     -> pipeline slot processing one tile
  scheduler     -> modulo rotation over slots (`coro_loop`; mispredict-free
                   by construction: control flow is compile-time scheduled)

A kernel built on this module keeps `depth` tiles in flight: while slot k's
data is crossing HBM<->VMEM, slots k-1, k-2, ... are being consumed — the
paper's interleaving of memory-driven coroutines. `depth=None` lets
`core.autotune.choose_depth` solve the depth from the spec's tile profile,
with the VMEM cap taken from the classified context bytes, for the active
`core.machine` profile. Every launched pipeline is wall-clocked and fed
back to `autotune.observe_pipeline` (always-on transfer telemetry) so the
adaptive re-solve learns from real runs without caller wiring.

Layering:

  CoroSpec / LoadStream / StoreStream  - the declaration (kernel authoring)
  coro_call                            - entry-point builder: resolves depth,
                                         derives scratch + semaphores, wraps
                                         pl.pallas_call, runs the pipeline
  coro_pipeline                        - the in-kernel engine (warmup /
                                         rotate / wait / consume / store
                                         drain) for hand-rolled kernels
  coro_loop                            - the bare rotation (no streams)
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import context as ctx_mod
from repro.core.machine import get_machine

__all__ = [
    "CoroRefs",
    "CoroSpec",
    "LoadStream",
    "StoreStream",
    "coro_call",
    "coro_loop",
    "coro_pipeline",
]


# ------------------------------------------------------------ declarations


@dataclasses.dataclass(frozen=True)
class LoadStream:
    """A decoupled input stream (aload/aset): slot buffer x depth.

    `src(ctx, tile)` returns the HBM ref-slice(s) feeding tile `tile`:
    a single slice (coarse-grained span request, §III-C case 1) or a list of
    `group` slices (an aset group — e.g. one DMA per gathered row), copied
    into consecutive `tile[0] // group`-row chunks of the slot buffer.

    `live(ctx, tile, j)`, where given, gates copy `j` of a tile (issue and
    wait alike): a ragged tile fetches only its live chunks, and the rest of
    the slot keeps what an earlier tile left there, for the body to mask.
    """

    name: str
    tile: Tuple[int, ...]
    dtype: Any
    src: Callable[..., Any]
    group: int = 1
    live: Optional[Callable[..., Any]] = None

    def __post_init__(self):
        _check_group(self)

    @property
    def nbytes(self) -> int:
        return int(math.prod(self.tile)) * int(np.dtype(self.dtype).itemsize)


def _check_group(stream) -> None:
    if stream.group < 1 or stream.tile[0] % stream.group:
        raise ValueError(
            f"stream {stream.name!r}: tile[0]={stream.tile[0]} must divide "
            f"into group={stream.group} equal chunks")


@dataclasses.dataclass(frozen=True)
class StoreStream:
    """A decoupled output stream (astore) with RMW drain semantics.

    The body writes the slot buffer; the builder starts the write-back DMAs
    to `dst(ctx, tile)` after the body, drains a slot's previous store
    before the body may rewrite it (tile >= depth), and drains every slot
    once more after the rotation retires (epilogue drain).
    """

    name: str
    tile: Tuple[int, ...]
    dtype: Any
    dst: Callable[..., Any]
    group: int = 1

    def __post_init__(self):
        _check_group(self)

    @property
    def nbytes(self) -> int:
        return int(math.prod(self.tile)) * int(np.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class CoroSpec:
    """Declarative description of one coroutine kernel family.

    The builder derives from it, per depth:
      * per-slot VMEM scratch for every stream ((depth, *tile), private
        context by construction),
      * one DMA semaphore array for the loads and one for the stores,
      * scratch for every materialized `vars` entry, shaped from
        `core.context.classify()` (private x depth, shared/sequential x 1),
      * the tile's `TileProfile` (DMA bytes + flops) for the depth solver.
    """

    name: str
    loads: Tuple[LoadStream, ...] = ()
    stores: Tuple[StoreStream, ...] = ()
    vars: Tuple[ctx_mod.VarSpec, ...] = ()
    flops_per_tile: float = 0.0

    def __post_init__(self):
        names = [s.name for s in self.loads] + [s.name for s in self.stores] \
            + [v.name for v in self.vars]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate stream/var names in spec: {names}")

    # ---- derived context (paper §III-B)

    def stream_vars(self) -> Tuple[ctx_mod.VarSpec, ...]:
        """Every stream slot is private context: one copy per in-flight tile."""
        return tuple(
            ctx_mod.VarSpec(name=s.name, nbytes=s.nbytes,
                            shape=tuple(s.tile), dtype=s.dtype)
            for s in (*self.loads, *self.stores)
        )

    def all_vars(self) -> Tuple[ctx_mod.VarSpec, ...]:
        return (*self.stream_vars(), *self.vars)

    def context_bytes(self, depth: int, *, baseline: bool = False) -> int:
        """Classified VMEM working set at `depth` (Fig. 15's comparison)."""
        return ctx_mod.context_bytes(self.all_vars(), depth, baseline=baseline)

    def tile_bytes(self) -> int:
        """HBM traffic per tile: every load and store stream moves its tile."""
        return sum(s.nbytes for s in (*self.loads, *self.stores))

    def profile(self):
        from repro.core.schedule import TileProfile  # local: avoid eager dep
        return TileProfile(tile_bytes=self.tile_bytes(),
                           flops_per_tile=float(self.flops_per_tile))

    # ---- derived allocation

    def scratch_shapes(self, depth: int) -> list:
        """The scratch list a kernel needs, in the canonical order
        [load slots..., store slots..., load sem, store sem, vars...]."""
        shapes: list = [
            pltpu.VMEM((depth, *s.tile), s.dtype)
            for s in (*self.loads, *self.stores)
        ]
        if self.loads:
            shapes.append(pltpu.SemaphoreType.DMA((depth,)))
        if self.stores:
            shapes.append(pltpu.SemaphoreType.DMA((depth,)))
        for v in self.materialized_vars():
            if ctx_mod.classify(v) is ctx_mod.VarClass.PRIVATE:
                shapes.append(pltpu.VMEM((depth, *v.shape), v.dtype))
            else:  # shared / sequential: one copy regardless of depth
                shapes.append(pltpu.VMEM(tuple(v.shape), v.dtype))
        return shapes

    def materialized_vars(self) -> Tuple[ctx_mod.VarSpec, ...]:
        return tuple(v for v in self.vars if v.shape is not None)


class CoroRefs:
    """Attribute namespace handed to spec callbacks: operand refs by their
    declared name, stream slot buffers and materialized vars by stream/var
    name."""

    def __init__(self, mapping):
        self.__dict__.update(mapping)


def _observe_pipeline(spec: "CoroSpec", t0: float, out, n_tiles: int,
                      depth: int) -> None:
    """Always-on transfer telemetry (ISSUE-6): wall-clock the launched
    pipeline and feed `autotune.observe_pipeline` (which drops the compile
    warmup and records wall/tiles as a per-tile transfer sample). Skipped
    under jit tracing — there is no wall clock to observe — and when
    `autotune.set_telemetry(False)`/``REPRO_TELEMETRY=0`` turned it off.

    The same wall clock becomes one ``pipeline:<kernel>`` span on the
    observability tracer (ISSUE-8), carrying the depth / n_tiles /
    classified context-bytes attributes DESIGN.md §2.5 documents — a null
    no-op when tracing is off."""
    from repro.core import autotune  # local: mirror coro_call's lazy import
    from repro.obs import trace

    if not autotune.telemetry_enabled():
        return
    leaves = jax.tree_util.tree_leaves(out)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return
    jax.block_until_ready(out)
    wall_s = time.perf_counter() - t0
    autotune.observe_pipeline(spec.name, wall_s, n_tiles)
    tracer = trace.get_tracer()
    end_us = tracer.now_us()
    tracer.complete(f"pipeline:{spec.name}", end_us - wall_s * 1e6,
                    wall_s * 1e6, tid=trace.TID_KERNEL, depth=depth,
                    n_tiles=n_tiles, context_bytes=spec.context_bytes(depth))


# ----------------------------------------------------------- the rotation


def coro_loop(
    n_tiles: int,
    depth: int,
    issue_fn: Callable[[Any, Any], None],
    consume_fn: Callable[[Any, Any, Any], Any],
    wait_fn: Callable[[Any, Any], None],
    carry_init: Any = 0,
    *,
    grid_step: Any = None,
):
    """Run the bare coroutine rotation over `n_tiles` with `depth` in flight.

    issue_fn(tile, slot)          - start the decoupled copies for `tile`
                                    into `slot` (aload/aset analogue)
    wait_fn(tile, slot)           - block until slot's copies landed (getfin)
    consume_fn(tile, slot, carry) - the coroutine body after resumption;
                                    returns updated carry

    `depth` is a Python int; `tile`/`slot` are traced int32 inside the
    steady-state loop. `n_tiles` is a Python int, or in fori mode a traced
    per-invocation count: `depth` must then already be clamped to its
    static bound, and each warm-up issue is guarded by `tile < n_tiles`.
    `depth <= 0` is a no-op that returns `carry_init` (spec-level entry
    points reject it earlier).

    Two drive modes share the one rotation (warmup / wait / consume /
    recycle) so no kernel re-implements the schedule:

    * fori mode (`grid_step=None`, default): the whole pipeline runs inside
      one kernel invocation via `jax.lax.fori_loop` over all tiles
      (decode_attention, moe_gmm, ssd_scan).
    * grid mode (`grid_step=pl.program_id(...)`): the Pallas grid supplies
      the tile loop — each grid step executes exactly one pipeline step for
      tile `grid_step`, relying on VMEM scratch persisting across steps.
      Warmup runs once under `pl.when(grid_step == 0)`
      (coro_gather, coro_scatter_add, stream_copy).
    """
    static = isinstance(n_tiles, int)
    if static:
        depth = min(depth, n_tiles)
    elif grid_step is not None:
        raise ValueError("a traced tile count needs fori mode")
    if depth <= 0:
        return carry_init

    def warmup():
        # launch the initial coroutine batch (paper's Init Block)
        for t in range(depth):
            if static:
                issue_fn(t, t)
            else:
                pl.when(t < n_tiles)(functools.partial(issue_fn, t, t))

    def step(t, carry):
        slot = jax.lax.rem(t, depth)
        # resume the coroutine whose data has arrived (bafin: the schedule is
        # compile-time so the "jump" costs nothing)
        wait_fn(t, slot)
        carry = consume_fn(t, slot, carry)

        # recycle the slot: launch the next iteration (paper's Return Block)
        @pl.when(t + depth < n_tiles)
        def _():
            issue_fn(t + depth, slot)

        return carry

    if grid_step is None:
        warmup()
        return jax.lax.fori_loop(0, n_tiles, step, carry_init)

    @pl.when(grid_step == 0)
    def _():
        warmup()

    return step(grid_step, carry_init)


# --------------------------------------------------------- stream plumbing


def _as_group(slices, group: int):
    if not isinstance(slices, (list, tuple)):
        slices = [slices]
    assert len(slices) == group, (len(slices), group)
    return slices


def _chunk(buf, slot, j: int, tile: Tuple[int, ...], group: int):
    rows = tile[0] // group
    return buf.at[slot, pl.ds(j * rows, rows)]


def _when_live(stream, ctx, t, j: int, fn) -> None:
    """Run `fn` for copy `j` of tile `t`, only where the stream's `live`
    predicate holds (always, for a stream without one)."""
    live = getattr(stream, "live", None)
    if live is None:
        fn()
    else:
        pl.when(live(ctx, t, j))(fn)


def _start_loads(stream: LoadStream, buf, sem, ctx, t, slot):
    srcs = _as_group(stream.src(ctx, t), stream.group)
    if stream.group == 1:
        copy = pltpu.make_async_copy(srcs[0], buf.at[slot], sem.at[slot])
        _when_live(stream, ctx, t, 0, copy.start)
        return
    for j, src in enumerate(srcs):
        copy = pltpu.make_async_copy(src, _chunk(buf, slot, j, stream.tile,
                                                 stream.group), sem.at[slot])
        _when_live(stream, ctx, t, j, copy.start)


def _wait_group(stream, buf, sem, slot, ctx=None, t=None):
    """Wait out a slot's outstanding copies (self-copy shaped waits): the
    arrival wait for a LoadStream (tile `t`'s live copies), the drain for a
    StoreStream."""
    if stream.group == 1:
        c = buf.at[slot]
        _when_live(stream, ctx, t, 0,
                   pltpu.make_async_copy(c, c, sem.at[slot]).wait)
        return
    for j in range(stream.group):
        c = _chunk(buf, slot, j, stream.tile, stream.group)
        _when_live(stream, ctx, t, j,
                   pltpu.make_async_copy(c, c, sem.at[slot]).wait)


def _start_stores(stream: StoreStream, buf, sem, ctx, t, slot):
    dsts = _as_group(stream.dst(ctx, t), stream.group)
    if stream.group == 1:
        pltpu.make_async_copy(buf.at[slot], dsts[0], sem.at[slot]).start()
        return
    for j, dst in enumerate(dsts):
        pltpu.make_async_copy(_chunk(buf, slot, j, stream.tile, stream.group),
                              dst, sem.at[slot]).start()


# ------------------------------------------------------- in-kernel engine


def coro_pipeline(
    spec: CoroSpec,
    ctx: CoroRefs,
    load_bufs: Sequence,
    store_bufs: Sequence,
    load_sem,
    store_sem,
    *,
    n_tiles: int,
    depth: int,
    body: Callable,
    prologue: Optional[Callable] = None,
    epilogue: Optional[Callable] = None,
    carry_init: Any = 0,
    grid_step: Any = None,
    live_tiles: Optional[Callable] = None,
):
    """Drive a `CoroSpec` inside a Pallas kernel.

    body(ctx, tile, slot, carry) -> carry  - the coroutine body; reads load
        slots (`ctx.<stream>[slot]`), writes store slots, updates vars.
    prologue(ctx) -> carry_init            - fori mode only: per-invocation
        reset (accumulators, recurrent state) before warmup.
    epilogue(ctx, carry)                   - fori mode only: after the final
        store drain (normalize, write residual outputs).
    live_tiles(ctx) -> traced int          - fori mode, loads only: the tiles
        this invocation walks (at most `n_tiles`, the static bound the
        depth is clamped to), read after the prologue.

    Store semantics (the RMW pipeline shared by coro_scatter_add and
    stream_copy): a slot's previous write-back is drained before the body
    may rewrite the slot (`tile >= depth`), new write-backs start right
    after the body, and every slot is drained once more when the rotation
    retires — under `pl.when(grid_step == n_tiles - 1)` in grid mode.
    """
    if depth is None or depth <= 0:
        raise ValueError(f"depth must be a positive int, got {depth}")
    depth = min(depth, n_tiles)
    if grid_step is not None and (prologue or epilogue):
        raise ValueError("prologue/epilogue require fori mode (grid_step=None)")
    if live_tiles is not None and spec.stores:
        raise ValueError("a traced tile count drives load-only pipelines")

    def issue(t, slot):
        for s, buf in zip(spec.loads, load_bufs):
            _start_loads(s, buf, load_sem, ctx, t, slot)

    def wait(t, slot):
        for s, buf in zip(spec.loads, load_bufs):
            _wait_group(s, buf, load_sem, slot, ctx, t)

    def consume(t, slot, carry):
        if spec.stores:
            # drain the slot's previous write-back before the body rewrites it
            @pl.when(t >= depth)
            def _():
                for s, buf in zip(spec.stores, store_bufs):
                    _wait_group(s, buf, store_sem, slot)

        carry = body(ctx, t, slot, carry)

        for s, buf in zip(spec.stores, store_bufs):
            _start_stores(s, buf, store_sem, ctx, t, slot)
        return carry

    if prologue is not None:
        carry_init = prologue(ctx)

    n_live = n_tiles if live_tiles is None else live_tiles(ctx)
    carry = coro_loop(n_live, depth, issue, consume, wait, carry_init,
                      grid_step=grid_step)

    if spec.stores:
        # final drain: every slot has exactly one outstanding store at the
        # end (earlier ones were drained before their buffer was rewritten)
        def drain_all():
            for slot in range(min(depth, n_tiles)):
                for s, buf in zip(spec.stores, store_bufs):
                    _wait_group(s, buf, store_sem, slot)

        if grid_step is None:
            drain_all()
        else:
            @pl.when(grid_step == n_tiles - 1)
            def _():
                drain_all()

    if epilogue is not None:
        epilogue(ctx, carry)
    return carry


# ---------------------------------------------------- entry-point builder


def coro_call(
    spec: CoroSpec,
    *operands,
    n_tiles: int,
    depth: Optional[int],
    body: Callable,
    arg_names: Sequence[str],
    grid: Tuple[int, ...],
    in_specs,
    out_specs,
    out_shape,
    drive_axis: Optional[int] = None,
    prologue: Optional[Callable] = None,
    epilogue: Optional[Callable] = None,
    carry_init: Any = 0,
    num_scalar_prefetch: int = 0,
    input_output_aliases=None,
    interpret: bool = False,
    live_tiles: Optional[Callable] = None,
):
    """Build and run the Pallas call for a `CoroSpec` kernel.

    `arg_names` names the kernel's operand refs in Pallas order (scalar-
    prefetch args, then inputs, then outputs); spec callbacks see them as
    `ctx.<name>`. `drive_axis` selects grid mode (that grid axis supplies
    the tile loop) vs fori mode (None: the pipeline runs inside each kernel
    invocation). In fori mode `live_tiles(ctx)` may bound each invocation
    to a traced tile count of its own; `n_tiles` is then the static bound.

    With `depth=None` the pipeline depth is solved by
    `core.autotune.choose_depth` from the spec's tile profile, the VMEM cap
    coming from the classified context bytes (`spec.all_vars()`); the
    result is clamped to `n_tiles` and recorded under `spec.name` for
    `autotune.last_choice`.
    """
    from repro.core import autotune  # local: autotune imports context only

    if depth is None:
        depth = autotune.choose_depth(spec.profile(), kernel=spec.name,
                                      vars=spec.all_vars())
        depth = min(int(depth), n_tiles)
        # re-record post-clamp so last_choice reports the depth actually run
        autotune.record_choice(spec.name, depth)
    elif depth <= 0:
        raise ValueError(f"depth must be >= 1, got {depth}")
    depth = min(int(depth), n_tiles)

    n_outs = len(out_shape) if isinstance(out_shape, (list, tuple)) else 1
    n_named = num_scalar_prefetch + len(in_specs) + n_outs
    if len(arg_names) != n_named:
        raise ValueError(
            f"arg_names has {len(arg_names)} names for {n_named} operand refs")
    if "pids" in arg_names:
        raise ValueError("'pids' is reserved for the program-id tuple")
    spec_names = {s.name for s in (*spec.loads, *spec.stores)} \
        | {v.name for v in spec.vars} | {"pids"}
    clash = spec_names & set(arg_names)
    if clash:
        raise ValueError(
            f"arg_names collide with spec stream/var names: {sorted(clash)} "
            "(the stream buffer would shadow the operand ref in ctx)")

    loads, stores = spec.loads, spec.stores
    shaped_vars = spec.materialized_vars()

    def attempt(run_depth: int):
        """One guarded attempt: re-derive scratch shapes for `run_depth`
        and build + launch the pallas_call (the guard's backoff ladder
        re-enters here with halved depths — DESIGN.md §2.7)."""
        scratch = spec.scratch_shapes(run_depth)

        def kernel(*refs):
            named = dict(zip(arg_names, refs[:n_named]))
            rest = list(refs[n_named:])
            load_bufs = tuple(rest[:len(loads)])
            del rest[:len(loads)]
            store_bufs = tuple(rest[:len(stores)])
            del rest[:len(stores)]
            load_sem = rest.pop(0) if loads else None
            store_sem = rest.pop(0) if stores else None
            for v in shaped_vars:
                named[v.name] = rest.pop(0)
            assert not rest, "scratch ref count mismatch"
            for s, buf in zip((*loads, *stores), (*load_bufs, *store_bufs)):
                named[s.name] = buf
            # program ids, evaluated once at kernel entry (they cannot be
            # read from inside the fori-mode loop body): ctx.pids[axis]
            named["pids"] = tuple(pl.program_id(a) for a in range(len(grid)))
            ctx = CoroRefs(named)
            grid_step = (pl.program_id(drive_axis)
                         if drive_axis is not None else None)
            coro_pipeline(spec, ctx, load_bufs, store_bufs, load_sem,
                          store_sem, n_tiles=n_tiles, depth=run_depth,
                          body=body, prologue=prologue, epilogue=epilogue,
                          carry_init=carry_init, grid_step=grid_step,
                          live_tiles=live_tiles)

        # Mosaic compiles the kernel under the same VMEM budget the depth
        # solver capped its context bytes at (autotune.choose_depth reads
        # machine.vmem_bytes); its default scoped limit (16 MiB on v5e)
        # would refuse the depths the solver hands out.
        kwargs = {"name": spec.name, "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=get_machine().vmem_bytes)}
        if input_output_aliases is not None:
            kwargs["input_output_aliases"] = input_output_aliases
        if num_scalar_prefetch:
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=num_scalar_prefetch,
                grid=grid,
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=scratch,
            )
            call = pl.pallas_call(kernel, grid_spec=grid_spec,
                                  out_shape=out_shape, interpret=interpret,
                                  **kwargs)
        else:
            call = pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                                  out_specs=out_specs, out_shape=out_shape,
                                  scratch_shapes=scratch, interpret=interpret,
                                  **kwargs)
        return call(*operands)

    from repro.core import guard  # local: guard imports obs/kernels lazily

    res = guard.guarded_call(spec, operands, attempt,
                             depth=depth, n_tiles=n_tiles)
    if res.fallback:
        # the jnp twin answered: no pipeline ran, so nothing to observe,
        # and last_choice keeps the depth the solver proposed
        return res.out
    if res.depth != depth:
        # backoff landed on a lower depth: report the depth actually run
        autotune.record_choice(spec.name, res.depth)
    _observe_pipeline(spec, res.t0, res.out, n_tiles, res.depth)
    return res.out
