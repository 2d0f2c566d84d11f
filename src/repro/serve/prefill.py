"""Chunked-prefill executor: prompt KV trickles through the paged pipeline.

The engine's original prefill was monolithic — the whole prompt through the
dense model, then a scatter of its KV into the pages. That stalls every
in-flight decode for the full prompt length and jit-compiles one program per
distinct prompt length. This module replaces it with fixed-size chunks driven
through the *paged* pipeline itself (`models.lm.prefill_chunk_paged`): each
chunk writes its KV rows directly into the request's pages and attends over
everything already resident — including shared prefix pages a cache hit put
in the table, which is what lets a request prefill only its suffix.

Compile discipline: chunk lengths are padded up to powers of two (floored at
one block), so the jit cache holds at most ``log2(max_chunk)`` entries per
table width instead of one per distinct length. `start` / `n_valid` are
traced scalars — moving a chunk along the prompt never recompiles.

Each executed chunk feeds `core.autotune.observe_pipeline` under the
``paged_prefill`` kernel key: wall clock over the page-tiles the chunk's
queries attended, the same latency ledger the decode rounds and the Pallas
pipelines share.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune


def bucket_len(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor) — the padded chunk length."""
    if n < 1:
        raise ValueError(f"bucket_len needs n >= 1, got {n}")
    return 1 << (max(int(n), int(floor), 1) - 1).bit_length()


class ChunkedPrefiller:
    """Owns the pow2-bucketed jit cache for paged prefill chunk steps."""

    def __init__(self, model, block_size: int, tracer):
        self.model = model
        self.block_size = int(block_size)
        self.tracer = tracer
        self._fns: Dict[Tuple[int, int], Any] = {}  # (padded_len, table_width)
        self._warm: set = set()  # keys whose jit compile was already paid
        self.chunks_run = 0

    def _fn(self, padded: int, table_width: int):
        key = (padded, table_width)
        fn = self._fns.get(key)
        if fn is None:
            model = self.model

            def paged_prefill_chunk(params, k_pools, v_pools, tokens, table,
                                    start, n_valid):
                return model.prefill_chunk_paged(
                    params, k_pools, v_pools, table, start,
                    {"tokens": tokens}, n_valid)

            fn = jax.jit(paged_prefill_chunk, donate_argnums=(1, 2))
            self._fns[key] = fn
        return fn

    def run_chunk(self, params, k_pools, v_pools, tokens, table, start: int,
                  n_valid: int):
        """Execute one prefill chunk. `tokens` is the [n_valid] real token
        slice; it is right-padded to its pow2 bucket here. `table` is the
        request's padded block table [table_width]. Returns
        (last_logits [1, V], k_pools, v_pools, wall_s). Spans:
        `prefill_dispatch` (ends when the jitted call returns, before the
        device finishes) and `prefill_wait` (the logits' readiness)."""
        if n_valid < 1:
            raise ValueError(f"chunk needs >= 1 tokens, got {n_valid}")
        padded = bucket_len(n_valid, floor=self.block_size)
        buf = np.zeros((1, padded), np.int32)
        buf[0, :n_valid] = np.asarray(tokens, np.int32).reshape(-1)
        table = np.asarray(table, np.int32).reshape(1, -1)
        key = (padded, table.shape[1])
        fn = self._fn(*key)
        warm = key in self._warm
        t0 = time.perf_counter()
        with self.tracer.span("prefill_dispatch"):
            logits, k_pools, v_pools = fn(
                params, k_pools, v_pools, jnp.asarray(buf),
                jnp.asarray(table), jnp.asarray(start, jnp.int32),
                jnp.asarray(n_valid, jnp.int32))
        with self.tracer.span("prefill_wait"):
            jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        self.chunks_run += 1
        self._warm.add(key)
        # telemetry: wall clock over the page-tiles the chunk attended; the
        # first call per bucket pays jit compile, so only warm calls record
        if warm and autotune.telemetry_enabled():
            tiles = -(-(int(start) + int(n_valid)) // self.block_size)
            autotune.observe_pipeline("paged_prefill", dt, n_tiles=max(tiles, 1))
        return logits, k_pools, v_pools, dt
