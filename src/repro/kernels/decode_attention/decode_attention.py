"""Flash-decode kernel: KV-cache streaming declared as a `CoroSpec`.

One decode token attends over a long KV cache living in HBM ("far memory").
Each KV block is one coroutine: its k/v DMAs form an aset group on a slot
semaphore; while block i is in flight, blocks i-1..i-depth+1 are being
consumed by the online-softmax accumulator. This is the paper's pattern at
its purest — latency-bound streaming with O(1) compute per byte. The paged
variant, `paged_flash_decode`, is the served decode attention on a TPU
(`models.common.paged_decode_attention`); its jnp twin runs elsewhere.

The declaration carries the kernel's whole §III-B context: the k/v slot
buffers are private (x depth, derived by the builder), while the m/l/acc
online-softmax accumulators are *commutative* updates — classified SHARED,
allocated once regardless of depth — and q is a read-only resident counted
against the budget but materialized from the operand block. The pipeline is
`core.coro.coro_call` in fori mode.

KV layout: the caches are streamed as ``[..., KH*D]`` — heads folded into
the lane dimension — so a tile's DMA is lane-dense at any head_dim (Mosaic
refuses a ``[..., KH, D]`` slice with D below the 128-lane tiling). Each KV
head is then a static ``D``-column window of the slot buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import context as ctx_mod
from repro.core.coro import CoroSpec, LoadStream, coro_call

NEG_INF = -1e30


def _softmax_vars(kh: int, g: int, d: int) -> tuple:
    h = kh * g
    return (
        # online-softmax state: commutative (max / rescaled-sum)
        # reductions -> SHARED, one copy regardless of depth
        ctx_mod.var("m", (kh, g, 1), jnp.float32,
                    carries_dependence=True, commutative=True),
        ctx_mod.var("l", (kh, g, 1), jnp.float32,
                    carries_dependence=True, commutative=True),
        ctx_mod.var("acc", (kh, g, d), jnp.float32,
                    carries_dependence=True, commutative=True),
        # the scaled query: read-only resident (operand block + f32 copy
        # in the loop carry); accounting-only, no scratch of its own
        ctx_mod.VarSpec("q_f32", nbytes=4 * (h * d + kh * g * d),
                        read_only=True),
    )


def decode_spec(blk: int, kh: int, g: int, d: int, dtype) -> CoroSpec:
    """KV block tile: k+v DMAs per slot; accumulators are depth-independent."""
    h = kh * g

    def kv_src(ref_name):
        def src(ctx, i):
            ref = getattr(ctx, ref_name)
            return ref.at[ctx.pids[0], pl.ds(i * blk, blk)]
        return src

    return CoroSpec(
        name="flash_decode",
        loads=(
            LoadStream("k", (blk, kh * d), dtype, src=kv_src("k_hbm")),
            LoadStream("v", (blk, kh * d), dtype, src=kv_src("v_hbm")),
        ),
        vars=_softmax_vars(kh, g, d),
        flops_per_tile=float(4 * blk * h * d),  # qk + pv per block
    )


def _init_softmax(ctx, kh: int, g: int, d: int):
    """Fresh accumulators for one batch row; returns the scaled f32 query."""
    ctx.m[...] = jnp.full_like(ctx.m, NEG_INF)
    ctx.l[...] = jnp.zeros_like(ctx.l)
    ctx.acc[...] = jnp.zeros_like(ctx.acc)
    return ctx.q_in[0].reshape(kh, g, d).astype(jnp.float32) * (d ** -0.5)


def _attend_block(ctx, k_ref, v_ref, qv, valid, kh: int, d: int) -> None:
    """Fold one KV block ([blk, KH*D] slot refs) into the online softmax,
    one KV head (a static D-column window) at a time. valid: [1, blk].
    The dots take the cache's dtype as operands and accumulate in f32, as
    the jnp twins do: bf16 pages feed the MXU as they are."""
    for j in range(kh):
        cols = pl.ds(j * d, d)
        k = k_ref[:, cols]                              # [blk, d]
        v = v_ref[:, cols]
        sc = jax.lax.dot_general(qv[j].astype(k.dtype), k,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [g, blk]
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = ctx.m[j]                               # [g, 1]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        ctx.l[j] = ctx.l[j] * corr + p.sum(axis=-1, keepdims=True)
        ctx.acc[j] = ctx.acc[j] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        ctx.m[j] = m_new


def _write_out(ctx, h: int, d: int) -> None:
    out = ctx.acc[...] / jnp.maximum(ctx.l[...], 1e-30)
    ctx.o[...] = out.reshape(1, h, d).astype(ctx.o.dtype)


def _lane_folded(cache):
    """[..., KH, D] -> [..., KH*D]: the lane-dense view the tiles stream."""
    return cache.reshape(*cache.shape[:-2], cache.shape[-2] * cache.shape[-1])


def flash_decode(q, k_cache, v_cache, pos, *, blk: int = 128,
                 depth: int | None = None, interpret: bool = True):
    """q: [B,H,D]; caches: [B,S,KH,D]; pos: scalar int32. Returns [B,H,D]."""
    bsz, h, d = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    assert s % blk == 0
    n_blocks = s // blk
    g = h // kh
    spec = decode_spec(blk, kh, g, d, k_cache.dtype)

    def prologue(ctx):
        return (_init_softmax(ctx, kh, g, d), ctx.pos[0])

    def body(ctx, i, slot, carry):
        qv, pos_v = carry
        kpos = i * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        _attend_block(ctx, ctx.k.at[slot], ctx.v.at[slot], qv,
                      kpos <= pos_v, kh, d)
        return carry

    def epilogue(ctx, carry):
        _write_out(ctx, h, d)

    return coro_call(
        spec, jnp.asarray([pos], jnp.int32), q,
        _lane_folded(k_cache), _lane_folded(v_cache),
        n_tiles=n_blocks, depth=depth, body=body,
        prologue=prologue, epilogue=epilogue,
        arg_names=("pos", "q_in", "k_hbm", "v_hbm", "o"),
        grid=(bsz,),
        num_scalar_prefetch=1,
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b, pos_ref: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b, pos_ref: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, d), q.dtype),
        interpret=interpret,
    )


# ----------------------------------------------------------- paged variant

# keys one tile of the paged kernel holds at the least: a tile of 16-token
# pages would pay the pipeline's per-tile cost for every 16 keys
KEYS_PER_TILE = 128


def window_blocks(window: int, block_size: int, table_width: int) -> int:
    """Blocks a windowed layer's decode reads per row: the fewest that
    always hold the last `window` positions, ceil(window / blk) + 1 (a
    window starting mid-block spans one more), or the whole table where
    that is narrower."""
    return min(-(-window // block_size) + 1, table_width)


def tile_pages(blk: int, max_pages: int) -> int:
    """Pages one tile of the paged kernel fetches: the fewest that hold
    `KEYS_PER_TILE` keys, and no more than a row can walk (`max_pages`)."""
    return max(1, min(-(-KEYS_PER_TILE // blk), max_pages))


def paged_decode_spec(blk: int, kh: int, g: int, d: int, dtype,
                      max_blocks: int, pages: int = 1) -> CoroSpec:
    """One tile = `pages` KV pages of one layer, fetched through the block
    table as one aset group of page DMAs.

    The serving engine's pager scatters each request's cache across a shared
    HBM block pool, one stacked pool per tensor ``[L, NB, blk, KH*D]``; copy
    j of tile i reads ``pool[layer, block_tables[b, first + i*pages + j]]``,
    a data-dependent page id read from scalar-prefetch memory (the paper's
    indirectly addressed aload), and only where that page is live for the
    row. Context is identical to the dense `decode_spec`: slots are private,
    the online-softmax accumulators are commutative -> SHARED, so every
    request in a round rides one pipeline at one solved depth.

    The layer and the row's bounds (`layer_id`, `first`, `n_pages`) are
    the kernel's, read once at entry onto `ctx` (`paged_flash_decode`).
    """
    h = kh * g

    def kv_src(ref_name):
        def src(ctx, i):
            ref = getattr(ctx, ref_name)
            base = ctx.pids[0] * max_blocks
            out = []
            for j in range(pages):
                # clamped into the row's table: a page past the row's end
                # is read from the table but never fetched (`live`)
                col = jnp.minimum(ctx.first + i * pages + j, max_blocks - 1)
                out.append(ref.at[ctx.layer_id, ctx.bt[base + col]])
            return out
        return src

    def live(ctx, i, j):
        return i * pages + j < ctx.n_pages

    return CoroSpec(
        name="paged_decode",
        loads=(
            LoadStream("k", (pages * blk, kh * d), dtype, src=kv_src("k_pools"),
                       group=pages, live=live),
            LoadStream("v", (pages * blk, kh * d), dtype, src=kv_src("v_pools"),
                       group=pages, live=live),
        ),
        vars=_softmax_vars(kh, g, d),
        flops_per_tile=float(4 * pages * blk * h * d),
    )


def paged_flash_decode(q, k_pools, v_pools, layer, block_tables, lengths, *,
                       window: int = 0, depth: int | None = None,
                       interpret: bool = True):
    """Flash-decode over one layer of a paged KV pool with ragged lengths.

    q: [B,H,D]; k_pools/v_pools: [L, NB, blk, KH*D], the stacked,
    lane-folded pools the engine serves, read in place; layer: [] int32
    (or a Python int); block_tables: [B, M] int32 (padded with the garbage
    block 0); lengths: [B] int32 — request b attends key positions <
    lengths[b], and with `window` > 0 only those >= lengths[b] - window.
    Returns [B,H,D]; a row with lengths == 0 reads nothing and gives zeros.

    Row b walks only its own pages: from the block of its first attended
    position to the block of its last, `tile_pages` pages a tile, so a
    row's work follows its length, not the table's width. The last tile
    fetches only its live pages; the masked rest of the slot holds pages an
    earlier tile fetched (the v slots start zeroed, so no uninitialized
    VMEM meets a zero weight).
    """
    bsz, h, d = q.shape
    blk, lanes = k_pools.shape[2], k_pools.shape[3]
    kh = lanes // d
    m = block_tables.shape[1]
    g = h // kh
    max_pages = window_blocks(window, blk, m) if window else m
    pages = tile_pages(blk, max_pages)
    spec = paged_decode_spec(blk, kh, g, d, k_pools.dtype, m, pages)
    lengths = jnp.asarray(lengths, jnp.int32)
    lows = (jnp.maximum(lengths - window, 0) if window
            else jnp.zeros_like(lengths))

    def prologue(ctx):
        row = ctx.pids[0]
        ctx.layer_id = ctx.layer[0]
        ctx.hi, ctx.lo = ctx.lens[row], ctx.lows[row]
        ctx.first = ctx.lo // blk
        ctx.n_pages = (ctx.hi + blk - 1) // blk - ctx.first

        @pl.when(row == 0)
        def _():
            ctx.v[...] = jnp.zeros_like(ctx.v)

        return _init_softmax(ctx, kh, g, d)

    def body(ctx, i, slot, qv):
        kpos = ((ctx.first + i * pages) * blk
                + jax.lax.broadcasted_iota(jnp.int32, (1, pages * blk), 1))
        valid = (kpos >= ctx.lo) & (kpos < ctx.hi)
        _attend_block(ctx, ctx.k.at[slot], ctx.v.at[slot], qv, valid, kh, d)
        return qv

    def epilogue(ctx, carry):
        _write_out(ctx, h, d)

    def row_spec(b, *_):
        return (b, 0, 0)

    return coro_call(
        spec,
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        jnp.asarray(block_tables, jnp.int32).reshape(-1),
        lengths, lows,
        q, k_pools, v_pools,
        n_tiles=-(-max_pages // pages), depth=depth, body=body,
        prologue=prologue, epilogue=epilogue,
        live_tiles=lambda ctx: (ctx.n_pages + pages - 1) // pages,
        arg_names=("layer", "bt", "lens", "lows", "q_in", "k_pools",
                   "v_pools", "o"),
        grid=(bsz,),
        num_scalar_prefetch=4,
        in_specs=[
            pl.BlockSpec((1, h, d), row_spec),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, d), row_spec),
        out_shape=jax.ShapeDtypeStruct((bsz, h, d), q.dtype),
        interpret=interpret,
    )
