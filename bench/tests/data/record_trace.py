"""Record the small profiler trace `bench/tests/test_trace_reduce.py` reads.

Run on a TPU; it writes `chiprun_out/trace_fixture/`. Two jitted programs
named like the server's (`paged_decode_round`, `paged_prefill_chunk`) run
inside numbered `round` annotations, with host sleeps between and inside
rounds, so the trace holds device ops, idle gaps inside rounds and waits
between them.
"""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def paged_decode_round(x, w):
    return jnp.tanh(x @ w) @ w.T


def paged_prefill_chunk(x, w):
    return (x @ w).sum()


def main(out: Path):
    dec, pre = jax.jit(paged_decode_round), jax.jit(paged_prefill_chunk)
    x = jnp.ones((512, 2048), jnp.bfloat16)
    w = jnp.ones((2048, 2048), jnp.bfloat16) * 1e-3
    jax.block_until_ready((dec(x, w), pre(x, w)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    for n in range(4):
        with jax.profiler.TraceAnnotation("round", n=n):
            jax.block_until_ready(dec(x, w))
            time.sleep(0.002)             # host work inside a round
            jax.block_until_ready(pre(x, w))
        time.sleep(0.003)                 # waiting between rounds
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(Path(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_fixture"))
