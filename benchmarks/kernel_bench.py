"""Kernel micro-benchmarks: shape sweeps in interpret mode + coalescing stats.

Interpret-mode wall time is NOT TPU performance (the kernels target TPU; this
container is CPU) — the derived columns that matter are correctness vs the
oracle, the coalescing ratio (requests saved, paper §III-C), and the
latency-aware depth the scheduler solves (paper §III-D analogue).

This is also the run-time feedback producer for the autotuner: measured
per-tile transfer samples are fed to `core.autotune.record_transfer`, and
the adaptive re-solve (`schedule.adaptive_depth`, the software analogue of
the paper's Return-Block dynamic scheduler) is reported next to the static
choice.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax.numpy as jnp

from benchmarks.common import csv_table, timed
from repro.core import autotune, guard
from repro.core.descriptors import plan_gather
from repro.core.machine import get_machine
from repro.core.schedule import TileProfile, solve_depth, achieved_bandwidth
from repro.kernels.coro_gather.coro_gather import row_gather_spec
from repro.kernels.coro_gather.ops import coro_gather
from repro.kernels.coro_gather.ref import gather_ref
from repro.kernels.coro_scatter_add.coro_scatter_add import scatter_add_spec
from repro.kernels.decode_attention.decode_attention import (
    decode_spec,
    paged_decode_spec,
    tile_pages,
)
from repro.kernels.decode_attention.ops import (
    decode_attention,
    paged_decode_attention,
)
from repro.kernels.moe_gmm.moe_gmm import gmm_spec
from repro.kernels.ssd_scan.ssd_scan import ssd_spec
from repro.kernels.stream_copy.ops import stream_triad
from repro.kernels.stream_copy.ref import triad_ref
from repro.kernels.stream_copy.stream_copy import triad_spec


def gather_rows():
    rng = np.random.RandomState(0)
    out = []
    for n_rows, d, n_idx in ((512, 128, 256), (2048, 256, 512)):
        table = jnp.asarray(rng.randn(n_rows, d), jnp.float32)
        idx = jnp.asarray(rng.randint(0, n_rows, n_idx), jnp.int32)
        res, us = timed(coro_gather, table, idx, repeats=1)
        ok = bool(jnp.allclose(res, gather_ref(table, idx)))
        depth = autotune.last_choice("row_gather")
        out.append(["coro_gather", f"{n_rows}x{d}/{n_idx}", round(us, 1), ok,
                    depth])
    return out


def coalesce_rows():
    rng = np.random.RandomState(1)
    out = []
    patterns = {
        "gups_random": rng.randint(0, 4096, 512),
        "stream_unit": np.arange(512),
        "hj_mixed": np.concatenate([np.arange(100, 300),
                                    rng.randint(0, 4096, 312)]),
    }
    for name, idx in patterns.items():
        plan = plan_gather(idx, span=8)
        out.append(["coalesce", name, plan.n_requests,
                    plan.requests_issued(), round(plan.coalescing_ratio(), 3)])
    return out


def schedule_rows():
    out = []
    for tag, tile_bytes, flops in (("gather_row", 8 * 2048 * 4, 64 * 8),
                                   ("kv_block", 2 * 128 * 8 * 128 * 2, 4 * 128 * 96 * 128),
                                   ("stream_tile", 2 * 128 * 512 * 4, 128 * 512)):
        p = TileProfile(tile_bytes=tile_bytes, flops_per_tile=float(flops))
        d = solve_depth(p)
        bw = achieved_bandwidth(p, d) / 1e9
        bw2 = achieved_bandwidth(p, 2) / 1e9
        out.append(["depth_solver", tag, d, round(bw, 1), round(bw2, 1)])
    return out


def adaptive_rows():
    """Feed measured per-tile transfer samples back into the autotuner.

    On this CPU container the 'measured latency' is interpret-mode overhead,
    orders slower than real HBM — which is exactly what makes the row useful:
    it shows the feedback path re-solving to a deeper (request-slot-capped)
    pipeline when observed latency dwarfs the data-sheet constant. The tile
    is big enough that the static solve sits below the cap, so the gap is
    visible.
    """
    rng = np.random.RandomState(3)
    table = jnp.asarray(rng.randn(512, 512), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 512, 64), jnp.int32)
    rows_per_tile = 8
    profile = autotune.profile_row_gather(rows_per_tile, 512, 4)
    static = autotune.choose_depth(profile, kernel="row_gather_bench")

    n_tiles = idx.shape[0] // rows_per_tile
    autotune.clear_samples("row_gather_bench")
    for _ in range(5):
        t0 = time.perf_counter()
        coro_gather(table, idx, rows_per_tile=rows_per_tile).block_until_ready()
        per_tile = (time.perf_counter() - t0) / n_tiles
        autotune.record_transfer("row_gather_bench", per_tile)
    adaptive = autotune.choose_depth(profile, kernel="row_gather_bench")
    n = len(autotune.transfer_samples("row_gather_bench"))
    autotune.clear_samples("row_gather_bench")
    return [["adaptive_depth", "row_gather", n, static, adaptive]]


def context_rows():
    """Derived context per kernel family (the §III-B classification at work).

    For each declared `CoroSpec`: the depth the autotuner solves from the
    spec, the classified context bytes at that depth, and the all-private
    baseline a conventional coroutine frame would occupy (Fig. 15's
    comparison) — the shared/sequential savings ratio in the last column.
    """
    f32 = jnp.float32
    specs = (
        row_gather_spec(8, 128, f32),
        scatter_add_spec(8, 128, f32),
        decode_spec(128, 8, 12, 128, f32),
        gmm_spec(64, 512, 128, f32, f_total=2048),
        ssd_spec(64, 8, 64, 128, f32, seq_len=2048),
        triad_spec(128, 512, f32),
    )
    out = []
    for spec in specs:
        depth = autotune.choose_depth(spec.profile(), vars=spec.all_vars())
        opt = spec.context_bytes(depth)
        base = spec.context_bytes(depth, baseline=True)
        out.append([spec.name, depth, opt, base, round(opt / base, 3)])
    return out


def paged_decode_rows():
    """Paged vs dense decode kernel at EQUAL total KV.

    The same [B, S] worth of KV is served once as dense per-request caches
    and once as a shuffled block pool addressed through block tables. The
    row reports the paged spec's classified context bytes, the depth the
    autotuner solves for it, and interpret-mode tokens/s for both kernels
    (relative, not TPU numbers — see module docstring).
    """
    rng = np.random.RandomState(4)
    out = []
    for bsz, s, kh, h, d, blk in ((2, 256, 2, 8, 16, 64),):
        q = jnp.asarray(rng.randn(bsz, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(bsz, s, kh, d), jnp.float32)
        v = jnp.asarray(rng.randn(bsz, s, kh, d), jnp.float32)
        # carve the same KV into a block pool with shuffled page placement
        m = s // blk
        nb = bsz * m + 1  # + garbage page 0
        ids = rng.permutation(np.arange(1, nb)).reshape(bsz, m)
        # one layer of the stacked, lane-folded pools the engine serves
        kp = jnp.zeros((1, nb, blk, kh * d), jnp.float32)
        vp = jnp.zeros((1, nb, blk, kh * d), jnp.float32)
        kp = kp.at[0, ids.reshape(-1)].set(k.reshape(bsz * m, blk, kh * d))
        vp = vp.at[0, ids.reshape(-1)].set(v.reshape(bsz * m, blk, kh * d))
        bt = jnp.asarray(ids, jnp.int32)
        lens = jnp.full((bsz,), s, jnp.int32)

        _, us_dense = timed(decode_attention, q, k, v, s - 1, blk=blk, repeats=1)
        res, us_paged = timed(paged_decode_attention, q, kp, vp, 0, bt, lens,
                              repeats=1)
        ref = decode_attention(q, k, v, s - 1, blk=blk)
        assert bool(jnp.allclose(res, ref, rtol=2e-5, atol=2e-5))
        g = h // kh
        spec = paged_decode_spec(blk, kh, g, d, jnp.float32, m,
                                 tile_pages(blk, m))
        depth = autotune.last_choice("paged_decode")
        out.append(["paged_decode", f"{bsz}x{s}x{kh}x{d}/blk{blk}",
                    spec.context_bytes(depth), depth,
                    round(bsz / (us_paged * 1e-6), 1),
                    round(bsz / (us_dense * 1e-6), 1)])
    return out


def prefix_decode_rows():
    """Shared-prefix serving at EQUAL KV budget, cache warm vs cold.

    The same 6-request workload (all opening with a 3-block system prompt)
    runs through the paged engine twice — prefix cache on, then off — with
    identical pool size and params. The row reports the warm run's hit rate,
    pages dedup'd, and decode tokens/s for both runs (interpret-mode
    relative numbers; the dedup counters are the point)."""
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import PagedServingEngine

    cfg = get_config("yi-6b").reduced().replace(dtype="float32",
                                                param_dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(5)
    blk, gen = 4, 6
    shared = list(rng.randint(0, cfg.vocab, 3 * blk))
    prompts = [shared + list(rng.randint(0, cfg.vocab, 3 + i))
               for i in range(6)]

    def run(prefix_cache):
        eng = PagedServingEngine(cfg, block_size=blk, num_blocks=48,
                                 params=params, max_in_flight=2,
                                 prefix_cache=prefix_cache)
        for p in prompts:
            eng.submit(p, max_new_tokens=gen)
        return eng.run()

    warm, cold = run(True), run(False)
    hit_rate = warm["prefix_hits"] / max(warm["requests"], 1)
    return [["prefix_decode", f"{len(prompts)}req/blk{blk}",
             round(hit_rate, 3), warm["blocks_shared"],
             f"{warm['blocks_allocated']}/{cold['blocks_allocated']}",
             warm["decode_tok_per_s"], cold["decode_tok_per_s"]]]


def triad_rows():
    rng = np.random.RandomState(2)
    b = jnp.asarray(rng.randn(1024, 64), jnp.float32)
    c = jnp.asarray(rng.randn(1024, 64), jnp.float32)
    res, us = timed(stream_triad, b, c, 2.5, repeats=1)
    # atol: fma reassociation leaves ~1e-6 absolute noise on near-zero entries
    ok = bool(jnp.allclose(res, triad_ref(b, c, 2.5), rtol=1e-5, atol=1e-5))
    return [["stream_triad", "1024x64", round(us, 1), ok,
             autotune.last_choice("stream_triad")]]


def _json_workloads():
    """One small run per kernel family: (spec for the static solve, thunk).

    Shapes mirror what the thunk actually launches so the static depth and
    the telemetry entry describe the same tile.
    """
    from repro.kernels.coro_scatter_add.ops import coro_scatter_add
    from repro.kernels.moe_gmm.ops import moe_gmm
    from repro.kernels.ssd_scan.ops import ssd

    rng = np.random.RandomState(7)
    f32 = jnp.float32

    table_g = jnp.asarray(rng.randn(256, 128), f32)
    idx_g = jnp.asarray(rng.randint(0, 256, 64), jnp.int32)

    table_s = jnp.asarray(rng.randn(256, 128), f32)
    idx_s = rng.randint(0, 256, 32)
    upd_s = jnp.asarray(rng.randn(32, 128), f32)

    q = jnp.asarray(rng.randn(2, 8, 16), f32)
    k = jnp.asarray(rng.randn(2, 128, 2, 16), f32)
    v = jnp.asarray(rng.randn(2, 128, 2, 16), f32)

    xs = jnp.asarray(rng.randn(2, 16, 64), f32)
    w = jnp.asarray(rng.randn(2, 64, 256), f32)

    x = jnp.asarray(rng.randn(1, 128, 2, 8), f32)
    dt = jnp.asarray(rng.rand(1, 128, 2), f32)
    A = jnp.asarray(-np.abs(rng.randn(2)), f32)
    B = jnp.asarray(rng.randn(1, 128, 16), f32)
    C = jnp.asarray(rng.randn(1, 128, 16), f32)

    tb = jnp.asarray(rng.randn(256, 64), f32)
    tc = jnp.asarray(rng.randn(256, 64), f32)

    return [
        (row_gather_spec(8, 128, f32),
         lambda: coro_gather(table_g, idx_g)),
        (scatter_add_spec(8, 128, f32),
         lambda: coro_scatter_add(table_s, idx_s, upd_s)),
        (decode_spec(64, 2, 4, 16, f32),
         lambda: decode_attention(q, k, v, 127, blk=64)),
        (gmm_spec(16, 64, 128, f32, f_total=256),
         lambda: moe_gmm(xs, w, f_tile=128)),
        (ssd_spec(64, 2, 8, 16, f32, seq_len=128),
         lambda: ssd(x, dt, A, B, C, chunk=64)),
        (triad_spec(128, 64, f32),
         lambda: stream_triad(tb, tc, 2.5)),
    ]


def json_report() -> dict:
    """Machine-stamped report (ISSUE-6 CI lane): active profile, per-kernel
    static solve vs the depth actually run, and observed p99 per-tile latency
    from the always-on telemetry. Each workload runs twice — the first run is
    compile warmup (dropped by the warmup skip), the second records.

    ISSUE-8 additions: each kernel carries the Fig. 14-style stall
    `breakdown` (compute / exposed transfer / scheduling gap attribution of
    its observed per-tile time against the active `MachineModel`), and the
    report embeds the default `obs.metrics` registry snapshot — the
    real-v5e measurement run reads hardware truth through this one report.

    ISSUE-10: the top-level `substrate` section is `core.guard.stats()` —
    guarded vs clean call counts, backoffs, fallbacks, parity checks. Under
    `--strict` a clean bench must show zero backoffs/fallbacks (the CI lane
    asserts it); anything else means the substrate degraded silently.
    """
    from repro.obs import metrics as obs_metrics

    m = get_machine()
    workloads = _json_workloads()
    for _, run in workloads:
        run()
        run()
    summ = autotune.telemetry_summary()
    kernels = {}
    for spec, _ in workloads:
        t = summ["kernels"].get(spec.name, {})
        # choose_depth AFTER the runs so it reports the static solve without
        # disturbing the telemetry the runs recorded
        kernels[spec.name] = {
            "static_depth": autotune.choose_depth(spec.profile(),
                                                  vars=spec.all_vars()),
            "ran_depth": t.get("depth"),
            "mode": t.get("mode"),
            "samples": t.get("samples", 0),
            "observed_p99_us": t.get("p99_us"),
            "breakdown": t.get("breakdown"),
        }
    return {"machine": m.name, "profile": m.summary(), "kernels": kernels,
            "substrate": guard.stats(),
            "metrics": obs_metrics.default_registry().snapshot()}


def table() -> str:
    s = csv_table(["kernel", "shape", "us_per_call", "allclose", "auto_depth"],
                  gather_rows() + triad_rows())
    s += csv_table(["pass", "pattern", "requests", "issued", "ratio"],
                   coalesce_rows())
    s += csv_table(["pass", "tile", "depth", "GBps_at_depth", "GBps_at_2"],
                   schedule_rows())
    s += csv_table(["pass", "kernel", "samples", "static_depth", "adaptive_depth"],
                   adaptive_rows())
    s += csv_table(["spec", "depth", "ctx_bytes", "ctx_baseline", "ratio"],
                   context_rows())
    s += csv_table(["pass", "shape", "ctx_bytes", "depth", "tok_per_s",
                    "dense_tok_per_s"], paged_decode_rows())
    s += csv_table(["pass", "workload", "hit_rate", "blocks_shared",
                    "alloc_warm/cold", "tok_per_s_warm", "tok_per_s_cold"],
                   prefix_decode_rows())
    return s


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="machine-stamped JSON report instead of CSV tables")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export the bench run's span trace as Chrome "
                         "trace-event JSON (open in https://ui.perfetto.dev)")
    ap.add_argument("--strict", action="store_true",
                    help="disable substrate degradation: any kernel "
                         "backoff/fallback/parity mismatch raises its typed "
                         "SubstrateError instead (CI parity lanes)")
    args = ap.parse_args(argv)
    if args.strict:
        guard.set_strict(True)
    if args.json:
        print(json.dumps(json_report(), indent=2))
    else:
        print(table())
    if args.trace:
        from repro.obs import trace as obs_trace

        obs_trace.get_tracer().export(args.trace)


if __name__ == "__main__":
    main()
