"""Grouped matmul for MoE experts: streamed weight tiles as a `CoroSpec`.

In expert-parallel MoE the *weights* are the far-memory objects: each local
expert's [dm, f] matrix is streamed HBM->VMEM tile-by-tile while the MXU
consumes the previous tile — the coroutine pipeline with weight tiles as the
in-flight context (CoroAMU's HJ build side). Each tile is a strided DMA
window [dm, f_tile] of the expert's weight matrix (no host-side relayout:
the weights stream from their native [E, dm, f] layout). The declaration is
one `LoadStream` plus accounting vars for the depth-independent residents
(the token block and the expert's full output block, both hint-SHARED); the
pipeline is `core.coro.coro_call` in fori mode with `depth` weight tiles in
flight (``depth=None`` solves it from the spec's profile via core.autotune).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import context as ctx_mod
from repro.core.coro import CoroSpec, LoadStream, coro_call


def gmm_spec(c: int, dm: int, f_tile: int, dtype,
             *, f_total: int | None = None) -> CoroSpec:
    """Streamed expert-weight tile; the token block AND the expert's full
    [c, f] output block are depth-independent VMEM residents."""
    itemsize = jnp.dtype(dtype).itemsize
    return CoroSpec(
        name="moe_gmm",
        loads=(LoadStream(
            "w", (dm, f_tile), dtype,
            src=lambda ctx, t: ctx.w_hbm.at[ctx.pids[0], :,
                                            pl.ds(t * f_tile, f_tile)],
        ),),
        vars=(
            # operand/output blocks resident across the whole expert:
            # accounting-only (materialized by the BlockSpecs, not scratch)
            ctx_mod.VarSpec("tokens", nbytes=c * dm * itemsize,
                            read_only=True),
            ctx_mod.VarSpec("y_block", nbytes=c * (f_total or f_tile) * itemsize,
                            hint=ctx_mod.VarClass.SHARED),
        ),
        flops_per_tile=float(2 * c * dm * f_tile),
    )


def gmm(tokens, weights, *, f_tile: int = 128, depth: int | None = None,
        interpret: bool = True):
    """tokens: [E, C, dm]; weights: [E, dm, f] -> [E, C, f]."""
    e, c, dm = tokens.shape
    f = weights.shape[-1]
    assert f % f_tile == 0
    n_tiles = f // f_tile
    spec = gmm_spec(c, dm, f_tile, weights.dtype, f_total=f)

    def prologue(ctx):
        return ctx.t[0]  # [c, dm] token block for this expert

    def body(ctx, t, slot, carry):
        # explicit DEFAULT: Mosaic refuses an fp32 contract precision on
        # bf16 operands, which a process-wide "highest" setting would ask
        ctx.o[0, :, pl.ds(t * f_tile, f_tile)] = jnp.einsum(
            "cd,df->cf", carry, ctx.w[slot],
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        ).astype(ctx.o.dtype)
        return carry

    return coro_call(
        spec, tokens, weights,
        n_tiles=n_tiles, depth=depth, body=body, prologue=prologue,
        arg_names=("t", "w_hbm", "o"),
        grid=(e,),
        in_specs=[
            pl.BlockSpec((1, c, dm), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, c, f), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), tokens.dtype),
        interpret=interpret,
    )
