#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` -> `workloads`) names a configuration
(`bench/configs/<config>.json`) and a traffic mix (`bench/traffic/<mix>.json`);
the configuration names its driver (`bench/drivers/<driver>.py`) and its
block family (`bench/reference/<reference>.py`), and each per-layer metric
has a reader (`bench/metrics/<metric>.py`). All are found by name, so a new
cell, mix, configuration or metric is a new file. A configuration of a
block the benchmark has not served before needs three: its file, its family
module (the plain reference's `logits`, the weight `leaves`, the work
`sizes`), and a mix if none fits.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the program's spans and
counters over the window and from a profiler trace of the window's last
seconds. Every run checks the served tokens against the plain reference
(`bench/check.py`) and prints each number compared beside its limit, as the
last lines of standard error and as the last key of the result.

Exits non-zero, printing no result, without a TPU, with fewer chips than
the cell needs, or on a chip missing from `bench/peaks.json`.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_SECONDS = 8.0      # the profiler covers the window's last seconds
CACHE_DIR = ROOT / ".jax_cache"


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    args: dict


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads."""
    cfg: dict
    sizes: object        # the family's `bench.work.Sizes`
    peaks: dict
    driver: object
    spans: list
    trace: dict = None


def load_module(path: Path, name: str):
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_family(cfg: dict, root: Path = ROOT):
    """The block family the configuration names: its reference, leaves and
    sizes (`bench/reference/<reference>.py` under `root`)."""
    name = cfg["reference"]
    return load_module(root / "bench" / "reference" / f"{name}.py",
                       f"bench_reference_{name}")


def cell_spec(workload: str, root: Path = ROOT):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return bench, cell, conf, mine(bench["end_to_end"]), mine(bench["per_layer"])


def configure_jax():
    import jax

    # one fixed directory inside the checkout: only a cell's first run compiles
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def window_spans(driver) -> list:
    """The program's obs spans and instants inside the window, on the
    host's perf_counter clock."""
    from repro.obs import trace as obs_trace

    tr = obs_trace.get_tracer()
    off = time.perf_counter() - tr.now_us() / 1e6
    out = []
    for ev in list(tr.events):
        if ev.get("ph") not in ("X", "i"):
            continue
        s = off + ev["ts"] / 1e6
        e = s + ev.get("dur", 0.0) / 1e6
        if driver.open <= s and e <= driver.close:
            out.append(Span(ev["name"], s, e, ev.get("args", {})))
    return out


def host_spans_for_trace(driver, spans) -> list:
    t0, t1 = driver.trace_window
    return [(s.name, s.start, s.end) for s in spans
            if s.end >= t0 and s.start <= t1 and s.end > s.start]


def run_cell(args, *, root: Path = ROOT, require_chip: bool = True,
             t_process: float = T_PROCESS) -> dict:
    _, cell, conf, e2e_specs, layer_specs = cell_spec(args.workload, root)
    cfg = json.loads((root / conf["file"]).read_text())
    from bench import traffic
    mix = traffic.load_mix(cell["traffic"], root / "bench" / "traffic")

    jax = configure_jax()
    family = load_family(cfg, root)
    from bench import chip
    if require_chip:
        devices, peaks = chip.check(cell["chips"])
    else:   # tests drive the rest of a run on whatever JAX has
        devices, peaks = jax.devices()[:cell["chips"]], chip.peaks_for("TPU v5 lite")

    from bench import check
    from bench.stats import CompileLog

    compile_log = CompileLog()
    drivers = load_module(BENCH / "drivers" / f"{cfg['serve']['driver']}.py",
                          f"bench_driver_{cfg['serve']['driver']}")
    drv = drivers.ServeDriver(cfg, family, mix, args.seed, args.seconds,
                              trace_s=TRACE_SECONDS if args.trace else 0.0)
    drv.run(t_process, compile_log)
    mem = drv.memory_after_build
    print(f"[bench] memory after weights and pool: "
          f"{mem.get('bytes_in_use')} of {mem.get('bytes_limit')} bytes; "
          f"round_width={drv.engine.round_width} "
          f"prefill_chunk={drv.engine.prefill_chunk} "
          f"token_budget={drv.engine.token_budget}", file=sys.stderr)
    e2e = drv.end_to_end()
    attempted, failed = drv.counts()
    stats = devices[0].memory_stats() or {}
    device = chip.describe(devices)
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    metrics, breakdown = {}, None
    if args.trace:
        from bench import trace_reduce
        spans = window_spans(drv)
        run = Run(cfg, family.sizes(cfg), peaks, drv, spans)
        if drv.profile_dir:
            pd = trace_reduce.load(drv.profile_dir)
            for line in trace_reduce.describe(pd)[:24]:
                print(f"[bench] trace plane | line | events: {line}",
                      file=sys.stderr)
            run.trace = trace_reduce.reduce(
                pd, host_spans=host_spans_for_trace(drv, spans),
                host_round_starts={i: r.start for i, r in enumerate(drv.rounds)})
            drv._tmp.cleanup()
            t = run.trace
            device["busy_s"] = t["busy_ns"] / 1e9
            device["window_s"] = drv.trace_window[1] - drv.trace_window[0]
            breakdown = {"device_ops": [[k, v] for k, v in t["top_ops"]],
                         "idle_gaps": [[k, v] for k, v in t["gaps"]]}
            print(f"[bench] trace: {t['n_ops']} ops, {t['n_modules']} "
                  f"programs, {len(t['rounds'])} rounds, device time by "
                  f"program {t['programs']}", file=sys.stderr)
        for m in layer_specs:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name'].replace('.', '_')}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e_specs:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    extra = {k: v for k, v in e2e.items() if k not in metrics}
    extra["compiles_in_window"] = [name for _, name, _ in drv.compiles]
    extra["failed_reasons"] = sorted({r.reason or r.state for r in drv.window_recs()
                                      if r.state != "done"})
    print(f"[bench] {args.workload} seed={args.seed}: {json.dumps(extra)}",
          file=sys.stderr)

    drv.free()
    sample = check.sample(drv.finished(), args.seed, cfg["check"]["requests"])
    comp = check.compare(family, cfg, args.seed, sample, pad_to=mix["context_cap"])
    correct, checks = check.judge(cfg, comp, failed)
    shown = {k: comp[k] for k in check.NUMBERS if k not in checks}
    print(f"[bench] read and not compared: {json.dumps(shown)}", file=sys.stderr)
    for k, v in checks.items():
        rel = ">=" if k == "tokens_compared" else "<="
        print(f"[check] {k} {v['value']} (limit {rel} {v['limit']})", file=sys.stderr)
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.chip import NoChip
    try:
        out = run_cell(args)
    except NoChip as e:
        print(f"[bench] cannot measure here: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        sys.exit(1)
