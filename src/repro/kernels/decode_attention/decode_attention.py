"""Flash-decode kernel: KV-cache streaming declared as a `CoroSpec`.

One decode token attends over a long KV cache living in HBM ("far memory").
Each KV block is one coroutine: its k/v DMAs form an aset group on a slot
semaphore; while block i is in flight, blocks i-1..i-depth+1 are being
consumed by the online-softmax accumulator. This is the paper's pattern at
its purest — latency-bound streaming with O(1) compute per byte. The served
path does not call it yet: `models.common` attends with a jnp gather (its
twin, `models.common.decode_attention`).

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
    one KV head (a static D-column window) at a time. valid: [1, blk]."""
    for j in range(kh):
        cols = pl.ds(j * d, d)
        k = k_ref[:, cols].astype(jnp.float32)          # [blk, d]
        v = v_ref[:, cols].astype(jnp.float32)
        sc = jax.lax.dot_general(qv[j], k, (((1,), (1,)), ((), ())))  # [g, blk]
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = ctx.m[j]                               # [g, 1]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        ctx.l[j] = ctx.l[j] * corr + p.sum(axis=-1, keepdims=True)
        ctx.acc[j] = ctx.acc[j] * corr + jnp.dot(p, v)
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


def paged_decode_spec(blk: int, kh: int, g: int, d: int, dtype,
                      max_blocks: int) -> CoroSpec:
    """One KV *page* = one coroutine tile, fetched through the block table.

    The serving engine's pager scatters each request's cache across a shared
    HBM block pool; the LoadStream src is gather-indexed — the tile's DMA
    source is `pool[block_tables[b, i]]`, a data-dependent page id read from
    scalar-prefetch memory (the paper's indirectly addressed aload). Context
    is identical to the dense `decode_spec`: slots are private, the
    online-softmax accumulators are commutative -> SHARED, so every request
    in a round rides one pipeline at one solved depth.
    """
    h = kh * g

    def kv_src(ref_name):
        def src(ctx, i):
            ref = getattr(ctx, ref_name)
            bid = ctx.bt[ctx.pids[0] * max_blocks + i]
            return ref.at[pl.ds(bid, 1)]
        return src

    return CoroSpec(
        name="paged_decode",
        loads=(
            LoadStream("k", (1, blk, kh * d), dtype, src=kv_src("k_pool")),
            LoadStream("v", (1, blk, kh * d), dtype, src=kv_src("v_pool")),
        ),
        vars=_softmax_vars(kh, g, d),
        flops_per_tile=float(4 * blk * h * d),
    )


def paged_flash_decode(q, k_pool, v_pool, block_tables, lengths, *,
                       depth: int | None = None, interpret: bool = True):
    """Flash-decode over a paged KV pool with ragged per-request lengths.

    q: [B,H,D]; k_pool/v_pool: [NB, blk, KH, D]; block_tables: [B, M] int32
    (pad with the reserved garbage block 0); lengths: [B] int32 — request b
    attends key positions < lengths[b]. Returns [B,H,D]; rows with
    lengths == 0 are garbage (round padding slots).

    Every request walks the same M tiles (tail pages fully masked), so one
    `coro_call` at one solved depth serves the whole ragged round — the
    block table only redirects each tile's DMA source.
    """
    bsz, h, d = q.shape
    blk, kh = k_pool.shape[1], k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    g = h // kh
    spec = paged_decode_spec(blk, kh, g, d, k_pool.dtype, max_blocks)

    def prologue(ctx):
        return (_init_softmax(ctx, kh, g, d), ctx.lens[ctx.pids[0]])

    def body(ctx, i, slot, carry):
        qv, len_v = carry
        kpos = i * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        _attend_block(ctx, ctx.k.at[slot, 0], ctx.v.at[slot, 0], qv,
                      kpos < len_v, kh, d)
        return carry

    def epilogue(ctx, carry):
        _write_out(ctx, h, d)

    return coro_call(
        spec,
        jnp.asarray(block_tables, jnp.int32).reshape(-1),
        jnp.asarray(lengths, jnp.int32),
        q, _lane_folded(k_pool), _lane_folded(v_pool),
        n_tiles=max_blocks, depth=depth, body=body,
        prologue=prologue, epilogue=epilogue,
        arg_names=("bt", "lens", "q_in", "k_pool", "v_pool", "o"),
        grid=(bsz,),
        num_scalar_prefetch=2,
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b, bt_ref, lens_ref: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b, bt_ref, lens_ref: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, d), q.dtype),
        interpret=interpret,
    )
