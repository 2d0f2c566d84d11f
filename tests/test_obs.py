"""Observability layer (ISSUE-8): Chrome-trace schema validation over an
end-to-end paged-serve run, the REPRO_TELEMETRY=0 null path, the shared
percentile/histogram/registry machinery, and the Fig. 14-style stall
breakdown's sum-to-wall-time invariant."""
import json

import numpy as np
import pytest

import repro.obs as obs
from repro.configs import get_config
from repro.core import autotune
from repro.core.schedule import TileProfile
from repro.obs import breakdown, metrics, trace
from repro.serve import PagedServingEngine


def _f32_cfg():
    return get_config("yi-6b").reduced().replace(dtype="float32",
                                                 param_dtype="float32")


def _pressured_prefix_run():
    """A paged run that exercises every instant event: a shared system
    prefix diverging mid-block (COW fork), a pool tight enough to reclaim
    cache-only pages (evict) and preempt an in-flight request. The later
    requests wait for the shared block's prefill before they admit, which
    staggers them: 12 blocks keeps the pool tight enough to preempt."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(3)
    shared = list(rng.integers(0, cfg.vocab, 6))  # 1.5 blocks at blk=4
    prompts = [shared + list(rng.integers(0, cfg.vocab, 18 + 3 * i))
               for i in range(3)]
    eng = PagedServingEngine(cfg, block_size=4, num_blocks=12,
                             prefix_cache=True)
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    stats = eng.run()
    return eng, stats


# ----------------------------------------------------------- trace schema


def _validate_chrome_trace(doc):
    """Schema-check a Chrome trace-event container: required keys per
    phase, and complete spans properly nested per track (each pair of "X"
    spans on one tid either disjoint or contained)."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    events = doc["traceEvents"]
    assert events, "trace must not be empty"
    for ev in events:
        assert ev["ph"] in ("X", "i", "b", "e"), ev
        for key in ("name", "ts", "pid", "tid"):
            assert key in ev, (key, ev)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
        if ev["ph"] in ("b", "e"):
            assert "id" in ev and "cat" in ev
    # nesting: on each tid, sort spans by (start, -dur); a running stack of
    # enclosing spans must always contain the next span or be disjoint
    by_tid = {}
    for ev in events:
        if ev["ph"] == "X":
            by_tid.setdefault(ev["tid"], []).append(ev)
    eps = 1e-3  # us slack: enter/exit clock reads are not atomic
    for tid, spans in by_tid.items():
        spans.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for ev in spans:
            while stack and ev["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] - eps:
                stack.pop()
            if stack:  # must be fully inside the enclosing span
                outer = stack[-1]
                assert ev["ts"] + ev["dur"] <= \
                    outer["ts"] + outer["dur"] + eps, (tid, outer, ev)
            stack.append(ev)
    return events


def test_trace_schema_and_lifecycle_events(tmp_path):
    """Acceptance: a prefix-cache paged run emits valid Chrome trace JSON
    with request-lifecycle spans, pipeline spans carrying depth/n_tiles
    attributes, and COW/evict/preempt instant events."""
    eng, stats = _pressured_prefix_run()
    path = tmp_path / "trace.json"
    trace.get_tracer().export(str(path))
    events = _validate_chrome_trace(json.loads(path.read_text()))

    names = {ev["name"] for ev in events}
    assert {"round", "decode_round", "prefill_chunk", "prefix_lookup",
            "admit"} <= names

    # the workload really did fork/evict/preempt (else the instants can't
    # be there) — and the instants are there
    assert stats["cow_forks"] >= 1 and stats["preemptions"] >= 1
    assert stats["cache_evictions"] >= 1
    instants = {ev["name"] for ev in events if ev["ph"] == "i"}
    assert {"cow_fork", "cache_evict", "preempt"} <= instants

    # request lifecycle: every submitted rid opens and closes an async span
    begins = {ev["id"] for ev in events
              if ev["ph"] == "b" and ev["name"] == "request"}
    ends = {ev["id"] for ev in events
            if ev["ph"] == "e" and ev["name"] == "request"}
    assert begins == ends == {0, 1, 2}

    # pipeline spans carry the §2.5 attributes
    pipes = [ev for ev in events if ev["name"] == "pipeline:paged_decode"]
    assert pipes
    for ev in pipes:
        assert ev["args"]["depth"] == stats["solved_depth"]
        assert ev["args"]["n_tiles"] >= 0
        assert ev["args"]["context_bytes"] > 0


def test_coro_call_pipeline_span_attributes():
    """A real kernel launch through coro_call lands one pipeline span with
    depth / n_tiles / context-bytes attributes on the kernel track."""
    import jax.numpy as jnp

    from repro.kernels.coro_gather.ops import coro_gather

    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.randn(64, 128), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 64, 32), jnp.int32)
    coro_gather(table, idx)
    evs = [ev for ev in trace.get_tracer().events
           if ev["name"] == "pipeline:row_gather"]
    assert evs, "coro_call must emit a pipeline span"
    ev = evs[-1]
    assert ev["tid"] == trace.TID_KERNEL
    assert ev["args"]["depth"] >= 1
    assert ev["args"]["n_tiles"] == 4  # 32 idx / 8 rows per tile
    assert ev["args"]["context_bytes"] > 0


def test_trace_export_via_launch_serve_flag(tmp_path):
    """`launch/serve.py --engine paged --trace out.json` writes a valid,
    non-empty Chrome trace (the ci.sh lane's contract)."""
    from repro.launch import serve as launch_serve

    path = tmp_path / "out.json"
    stats = launch_serve.main([
        "--arch", "yi-6b", "--reduced", "--batch", "2",
        "--prompt-len", "16", "--gen", "3", "--engine", "paged",
        "--block-size", "4", "--trace", str(path)])
    assert stats["trace"] == str(path)
    assert path.stat().st_size > 0
    events = _validate_chrome_trace(json.loads(path.read_text()))
    names = {ev["name"] for ev in events}
    assert "round" in names and "pipeline:paged_decode" in names


# ------------------------------------------------------------- null path


def test_disabled_tracer_and_registry_allocate_nothing():
    """REPRO_TELEMETRY=0 path: module-level null objects, no event storage,
    no per-call allocation (span() returns one shared context manager)."""
    obs.set_enabled(False)
    tracer = trace.get_tracer()
    assert tracer is trace.NULL_TRACER
    s1 = tracer.span("a", depth=3)
    with tracer.span("b"):
        tracer.instant("cow_fork", src=1, dst=2)
        tracer.complete("pipeline:x", 0.0, 1.0, depth=2)
        tracer.begin_async("request", 0)
        tracer.end_async("request", 0)
    assert tracer.span("c") is s1  # the one shared null span: no allocation
    assert len(tracer.events) == 0 and tracer.to_dict()["traceEvents"] == []

    reg = metrics.new_registry()
    assert reg is metrics.NULL_REGISTRY
    c = reg.counter("x")
    c.inc(5)
    h = reg.histogram("y")
    h.observe(1.0)
    assert c.value == 0 and h.count == 0 and h.samples == []
    assert reg.counter("z") is c  # shared singleton metric objects
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert metrics.default_registry() is metrics.NULL_REGISTRY
    assert reg.prometheus_text() == ""

    # an engine built while disabled still serves correctly; its stats
    # degrade to registry zeros rather than erroring
    cfg = _f32_cfg()
    eng = PagedServingEngine(cfg, block_size=4, num_blocks=32,
                             prefix_cache=True)
    eng.submit(list(range(1, 9)), max_new_tokens=2)
    stats = eng.run()
    assert stats["completed"] == 1
    assert stats["p50_ms"] == 0.0 and stats["prefix_hits"] == 0
    assert len(trace.get_tracer().events) == 0

    obs.set_enabled(True)
    assert trace.get_tracer() is not trace.NULL_TRACER


def test_env_seeds_disabled_state(monkeypatch):
    monkeypatch.setenv("REPRO_TELEMETRY", "0")
    obs.reset()
    assert trace.get_tracer() is trace.NULL_TRACER
    assert metrics.default_registry() is metrics.NULL_REGISTRY
    monkeypatch.setenv("REPRO_TELEMETRY", "1")
    obs.reset()
    assert trace.get_tracer() is not trace.NULL_TRACER


# ------------------------------------------------------ metrics registry


def test_histogram_percentiles_and_report():
    reg = metrics.MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    xs = [0.05, 0.5, 0.7, 2.0, 20.0]
    for x in xs:
        h.observe(x)
    assert h.count == 5 and h.bucket_counts == [1, 2, 1, 1]
    assert h.percentile(0.50) == metrics.percentile(xs, 0.50) == 0.7
    assert h.percentile(0.99) == 20.0
    rep = h.report()
    assert rep["count"] == 5 and rep["p50"] == 0.7

    # the sample ring is bounded like autotune's store
    h2 = metrics.Histogram("b", buckets=(1.0,), max_samples=8)
    for i in range(100):
        h2.observe(float(i))
    assert len(h2.samples) == 8 and h2.count == 100
    assert h2.samples == [float(i) for i in range(92, 100)]


def test_latency_report_is_the_one_shared_implementation():
    """The engine and launch.serve percentile copies are gone: both route
    through obs.metrics.latency_report."""
    from repro.launch import serve as launch_serve
    from repro.serve import engine as serve_engine

    assert serve_engine.latency_report is metrics.latency_report
    assert launch_serve.latency_report is metrics.latency_report
    assert not hasattr(autotune, "_percentile")
    rep = metrics.latency_report([0.001, 0.002, 0.003])
    assert rep == {"p50_ms": 2.0, "p99_ms": 3.0, "mean_ms": 2.0}
    assert metrics.latency_report([]) == {"p50_ms": 0.0, "p99_ms": 0.0,
                                          "mean_ms": 0.0}


def test_registry_snapshot_prometheus_and_views():
    reg = metrics.MetricsRegistry()
    reg.counter("serve.prefix_hits").inc(3)
    reg.gauge("pool.free_blocks").set(7)
    h = reg.histogram("serve.token_latency_s", buckets=(0.01, 0.1))
    h.observe(0.05)
    reg.view("extra", lambda: {"k": 1})
    snap = reg.snapshot()
    assert snap["counters"]["serve.prefix_hits"] == 3
    assert snap["gauges"]["pool.free_blocks"] == 7
    assert snap["histograms"]["serve.token_latency_s"]["count"] == 1
    assert snap["extra"] == {"k": 1}

    text = reg.prometheus_text()
    assert "# TYPE serve_prefix_hits counter" in text
    assert "serve_prefix_hits 3" in text
    assert '# TYPE serve_token_latency_s histogram' in text
    assert 'serve_token_latency_s_bucket{le="0.01"} 0' in text
    assert 'serve_token_latency_s_bucket{le="+Inf"} 1' in text

    with pytest.raises(TypeError):
        reg.gauge("serve.prefix_hits")  # name already a counter


def test_default_registry_serves_autotune_view():
    """telemetry_summary is a VIEW of the default registry: one snapshot
    covers the kernel feedback loop (ISSUE-8 acceptance)."""
    autotune.record_transfer("viewk", 1e-4)
    snap = metrics.default_registry().snapshot()
    assert snap["autotune"]["kernels"]["viewk"]["samples"] == 1
    assert snap["autotune"] == autotune.telemetry_summary()


def test_engine_stats_are_registry_views():
    eng, stats = _pressured_prefix_run()
    snap = eng.metrics.snapshot()
    assert snap["counters"]["serve.prefix_hits"] == stats["prefix_hits"] > 0
    assert snap["counters"]["serve.cow_forks"] == stats["cow_forks"] >= 1
    assert snap["histograms"]["serve.token_latency_s"]["count"] > 0
    assert snap["histograms"]["serve.ttft_s"]["count"] == stats["completed"]
    assert "serve_cow_forks" in eng.metrics.prometheus_text()
    # two engines never share a registry
    assert PagedServingEngine(
        _f32_cfg(), block_size=4, num_blocks=8).metrics is not eng.metrics


# ----------------------------------------------------- stall breakdown


def test_breakdown_attribution_sums_to_observed():
    """Acceptance: compute + transfer + gap == observed wall time (within
    10%; exact by construction, modulo rounding) across regimes."""
    p = TileProfile(tile_bytes=1 << 20, flops_per_tile=1e6)
    for depth in (1, 2, 8, 64):
        for w in (1e-6, 5e-5, 3e-3):
            bd = breakdown.attribute(p, depth, w)
            total = bd["compute_us"] + bd["transfer_us"] + bd["gap_us"]
            assert total == pytest.approx(bd["observed_us"], rel=0.1)
            assert bd["compute_frac"] + bd["transfer_frac"] + \
                bd["gap_frac"] == pytest.approx(1.0, abs=0.01)
    # a compute-bound tile at generous depth attributes mostly to compute
    heavy = TileProfile(tile_bytes=1024, flops_per_tile=1e9)
    from repro.core.schedule import tile_compute_s
    tc = tile_compute_s(heavy)
    bd = breakdown.attribute(heavy, 64, tc * 1.01)
    assert bd["compute_frac"] > 0.9


def test_breakdown_in_telemetry_summary_and_report():
    """choose_depth records the tile profile; once samples land, the
    summary (and stall_breakdown over it) carries the attribution."""
    p = TileProfile(tile_bytes=1 << 16, flops_per_tile=1e5)
    depth = autotune.choose_depth(p, kernel="bdk")
    assert autotune.last_profile("bdk") == p
    for _ in range(4):
        autotune.record_transfer("bdk", 2e-4)
    entry = autotune.telemetry_summary()["kernels"]["bdk"]
    bd = entry["breakdown"]
    assert bd["depth"] == depth
    assert bd["observed_us"] == pytest.approx(entry["p50_us"], rel=1e-6)
    total = bd["compute_us"] + bd["transfer_us"] + bd["gap_us"]
    assert total == pytest.approx(bd["observed_us"], rel=0.1)

    rep = breakdown.stall_breakdown()
    assert rep["kernels"]["bdk"] == bd

    # kernels observed without a profile report unattributed time
    autotune.record_transfer("no_profile_kernel", 1e-4)
    rep = breakdown.stall_breakdown()
    assert rep["kernels"]["no_profile_kernel"]["unattributed"] is True


def test_breakdown_sums_for_live_paged_decode():
    """End-to-end half of the acceptance criterion: the breakdown the
    serving engine's decode rounds produce sums to their observed per-tile
    wall time."""
    _eng, _stats = _pressured_prefix_run()
    entry = autotune.telemetry_summary()["kernels"]["paged_decode"]
    assert entry["samples"] > 0
    bd = entry["breakdown"]
    total = bd["compute_us"] + bd["transfer_us"] + bd["gap_us"]
    assert total == pytest.approx(bd["observed_us"], rel=0.1)


def test_kernel_bench_json_carries_breakdown_and_metrics(tmp_path):
    """`kernel_bench --json` embeds the registry snapshot and per-kernel
    breakdowns; `--trace` writes a valid trace of the bench run."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.kernel_bench import json_report

    rep = json_report()
    assert "metrics" in rep and "autotune" in rep["metrics"]
    entry = rep["kernels"]["row_gather"]
    assert entry["samples"] > 0 and entry["breakdown"] is not None
    bd = entry["breakdown"]
    total = bd["compute_us"] + bd["transfer_us"] + bd["gap_us"]
    assert total == pytest.approx(bd["observed_us"], rel=0.1)


def test_tracer_ring_bounds_memory():
    tr = trace.Tracer(capacity=16)
    for i in range(100):
        tr.instant(f"e{i}")
    assert len(tr.events) == 16 and tr.dropped == 84
    assert [ev["name"] for ev in tr.events][0] == "e84"
