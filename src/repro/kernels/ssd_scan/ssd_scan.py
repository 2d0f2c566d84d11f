"""Chunked SSD (Mamba-2) scan kernel declared as a `CoroSpec`.

Grid = (batch,): each grid step scans one sequence. Chunk inputs (x, dt, B,
C) are four `LoadStream`s — each chunk's four DMAs form one aset group on a
slot semaphore and `depth` chunks are in flight while earlier chunks
compute. The recurrent state [H,P,N] is declared as a *sequential* context
var (order-dependent update — core/context.py classifies it one-copy,
depth-independent) and the builder derives its scratch; it resets at each
batch element in the prologue. ``depth=None`` solves the depth from the
spec's chunk profile via core.autotune.

Note the intra-chunk math is order-free; only the [H,P,N] state carries the
sequential dependence, so deep pipelining of chunk *loads* is safe.

x and y stream as ``[b, s, H*P]`` (heads folded into the lane dimension, so
every tile is lane-dense at P=64); each head is a static P-column window.
dt and A carry the head axis zero-padded to whole 128-lane tiles.
The body is written in the operations Mosaic lowers: the prefix sum of
``dt*A`` is a matmul with the lower-triangular ones, and the pairwise
``cs[t] - cs[s]`` comes from a broadcast and a transpose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import context as ctx_mod
from repro.core.coro import CoroSpec, LoadStream, coro_call

NEG_INF = -1e30
_HI = jax.lax.Precision.HIGHEST
_LANES = 128


def _lane_pad(nh: int) -> int:
    """dt / A stream with the head axis padded to whole 128-lane tiles."""
    return -(-nh // _LANES) * _LANES


def ssd_spec(chunk: int, nh: int, p: int, n: int, dtype,
             *, seq_len: int | None = None) -> CoroSpec:
    """Chunk tile: x/dt/B/C stream per slot; the recurrent state is
    sequential (one copy) and the per-batch y/h-out blocks are residents."""
    itemsize = jnp.dtype(dtype).itemsize

    def chunk_src(ref_name):
        def src(ctx, t):
            ref = getattr(ctx, ref_name)
            return ref.at[ctx.pids[0], pl.ds(t * chunk, chunk)]
        return src

    return CoroSpec(
        name="ssd_scan",
        loads=(
            LoadStream("x", (chunk, nh * p), dtype, src=chunk_src("x_hbm")),
            LoadStream("dt", (chunk, _lane_pad(nh)), dtype,
                       src=chunk_src("dt_hbm")),
            LoadStream("bmat", (chunk, n), dtype, src=chunk_src("b_hbm")),
            LoadStream("cmat", (chunk, n), dtype, src=chunk_src("c_hbm")),
        ),
        vars=(
            # recurrent state: order-dependent update -> SEQUENTIAL, one copy
            ctx_mod.var("h", (nh, p, n), jnp.float32,
                        carries_dependence=True),
            # per-batch residents: h-out f32 block + y output block
            ctx_mod.VarSpec("h_out_block", nbytes=4 * nh * p * n,
                            hint=ctx_mod.VarClass.SHARED),
            ctx_mod.VarSpec("y_block",
                            nbytes=(seq_len or chunk) * nh * p * itemsize,
                            hint=ctx_mod.VarClass.SHARED),
        ),
        flops_per_tile=float(2 * chunk * chunk * (n + nh * p)),
    )


def _dot_nt(a, b):
    """a @ b.T without materializing the transpose."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64, depth: int | None = None,
             interpret: bool = True):
    """Batched SSD. x:[b,s,nh,p] dt:[b,s,nh] A:[nh] B,C:[b,s,n].

    Returns (y [b,s,nh,p], h_final [b,nh,p,n]).
    """
    bsz, s, nh, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    n_chunks = s // chunk
    spec = ssd_spec(chunk, nh, p, n, x.dtype, seq_len=s)

    def prologue(ctx):
        ctx.h[...] = jnp.zeros_like(ctx.h)  # fresh state per batch element
        A_f = ctx.a[...].astype(jnp.float32)          # [1, nh_pad]
        causal = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
        return (A_f, causal, jnp.ones((p, chunk), jnp.float32))

    def body(ctx, tile, slot, carry):
        A_f, causal, ones = carry
        dtc = ctx.dt[slot].astype(jnp.float32)   # [chunk, nh_pad]
        Bc = ctx.bmat[slot].astype(jnp.float32)  # [chunk, n]
        Cc = ctx.cmat[slot].astype(jnp.float32)  # [chunk, n]

        dA = dtc * A_f                           # [chunk, nh] (<=0)
        # prefix sums as matmuls (Mosaic has no cumsum): cs[t] sums
        # dA[<=t], tail[t] = total - cs[t] sums dA[>t], and every row of
        # tot is the chunk total (a lane-broadcastable [p, 1] per head)
        cs = jnp.dot(causal, dA, precision=_HI)          # [chunk, nh]
        tail = jnp.dot(1.0 - causal, dA, precision=_HI)  # [chunk, nh]
        tot = jnp.dot(ones, dA, precision=_HI)           # [p, nh]
        scores = _dot_nt(Cc, Bc)                 # [chunk, chunk]
        rows = pl.ds(tile * chunk, chunk)

        for hh in range(nh):
            cols = pl.ds(hh * p, p)
            cs_h = cs[:, hh:hh + 1]              # [chunk, 1]
            dtx = ctx.x[slot, :, cols].astype(jnp.float32) * dtc[:, hh:hh + 1]
            seg = jnp.broadcast_to(cs_h, (chunk, chunk))
            L = jnp.exp(jnp.where(causal > 0, seg - seg.T, NEG_INF))
            h_prev = ctx.h[hh]                   # [p, n]
            y_intra = jnp.dot(scores * L, dtx, precision=_HI)
            y_inter = jnp.exp(cs_h) * _dot_nt(Cc, h_prev)
            ctx.y[0, rows, cols] = (y_intra + y_inter).astype(ctx.y.dtype)
            decay_to_end = jnp.exp(tail[:, hh:hh + 1])            # [chunk, 1]
            s_chunk = jax.lax.dot_general(                        # [p, n]
                dtx, Bc * decay_to_end, (((0,), (0,)), ((), ())),
                precision=_HI)
            ctx.h[hh] = h_prev * jnp.exp(tot[:, hh:hh + 1]) + s_chunk
        return carry

    def epilogue(ctx, carry):
        ctx.h_out[...] = ctx.h[...].astype(ctx.h_out.dtype)[None]

    pad = _lane_pad(nh) - nh
    dt_p = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
    a_p = jnp.pad(A.reshape(1, nh), ((0, 0), (0, pad)))
    y, h_final = coro_call(
        spec, x.reshape(bsz, s, nh * p), dt_p, a_p, B, C,
        n_tiles=n_chunks, depth=depth, body=body,
        prologue=prologue, epilogue=epilogue,
        arg_names=("x_hbm", "dt_hbm", "a", "b_hbm", "c_hbm", "y", "h_out"),
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # x
            pl.BlockSpec(memory_space=pl.ANY),            # dt
            pl.BlockSpec((1, nh + pad), lambda b: (0, 0)),  # A (resident)
            pl.BlockSpec(memory_space=pl.ANY),            # B
            pl.BlockSpec(memory_space=pl.ANY),            # C
        ],
        out_specs=[
            pl.BlockSpec((1, s, nh * p), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, nh, p, n), lambda b: (b, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, nh * p), x.dtype),
            jax.ShapeDtypeStruct((bsz, nh, p, n), jnp.float32),
        ],
        interpret=interpret,
    )
    return y.reshape(bsz, s, nh, p), h_final
