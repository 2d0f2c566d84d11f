#!/usr/bin/env python3
"""Where a cell's device programs spend their time, by model scope, and
what the host was doing while the device idled inside a round.

  python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as `bench/run.py --trace 1` does (set-up, the window, the
profiler over its last seconds), keeps the trace, and prints one JSON line:

* ``programs``: for each program, its device time per run (module events)
  and the time of its operations split by the model scope of each
  (`PagedServingEngine.op_scopes()`: ``attention``, ``ffn``, ``embed``,
  ``unembed``, ``other``, and their parts), with the heaviest operations;
* ``idle``: the device-idle time inside the benchmark's ``round``
  annotations, split by the innermost ``repro/`` span the program's tracer
  put on the profiler's host plane (the same clock as the device planes),
  and the share of it under a span other than ``repro/round``;
* the window's ``output_tok_s``, the per-layer ``round_host_ms`` and the
  tracer's ``dropped`` count.

It compiles each program again for `op_scopes()` after the window. Needs a
TPU, as `bench/run.py` does; the trace reading below needs none.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import bisect  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import (Dict, Iterable, List, Optional, Sequence,  # noqa: E402
                    Tuple)

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import trace_reduce as R  # noqa: E402

Span = Tuple[str, float, float]
SPAN_PREFIX = "repro/"


def instruction(op: str) -> str:
    """``%copy.88 = bf16[...]{...} copy(...)`` -> ``copy.88``."""
    return op.partition(" = ")[0].strip().lstrip("%")


def program_of(module: str, programs: Sequence[str]) -> Optional[str]:
    return next((p for p in programs if p in module), None)


def op_device_ns(pd, programs: Sequence[str]) -> Tuple[
        Dict[str, Dict[str, float]], Dict[str, List[float]]]:
    """Device ns of every operation per program and HLO instruction, and
    the duration of each run (module event) of each program. Loops and
    calls wrap other operations and are left out, as in `R.reduce`."""
    ops, modules, _ = R.events(pd)
    starts = [m[0] for m in modules]
    runs: Dict[str, List[float]] = {}
    for a, b, name in modules:
        p = program_of(name, programs)
        if p is not None:
            runs.setdefault(p, []).append(b - a)
    by_op: Dict[str, Dict[str, float]] = {}
    for a, b, name in ops:
        if name.startswith(R.CONTROL_FLOW):
            continue
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or modules[i][1] < b:
            continue
        p = program_of(modules[i][2], programs)
        if p is not None:
            d = by_op.setdefault(p, {})
            d[instruction(name)] = d.get(instruction(name), 0.0) + (b - a)
    return by_op, runs


def by_scope(op_ns: Dict[str, float], scopes: Dict[str, str],
             depth: int = 1) -> Dict[str, float]:
    """Sum of `op_ns` by the first `depth` parts of each instruction's
    scope; an instruction `scopes` does not hold counts as ``unmatched``."""
    out: Dict[str, float] = {}
    for op, ns in op_ns.items():
        scope = "/".join(scopes.get(op, "unmatched").split("/")[:depth])
        out[scope] = out.get(scope, 0.0) + ns
    return out


def host_spans(pd, prefix: str = SPAN_PREFIX) -> List[Span]:
    """(name without the prefix, start ns, end ns) of every host event
    whose name starts with `prefix`."""
    return sorted(((ev.name[len(prefix):], ev.start_ns,
                    ev.start_ns + ev.duration_ns)
                   for plane in pd.planes if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith(prefix)), key=lambda s: s[1])


def in_round_idle(busy: Sequence[Tuple[float, float]],
                  rounds: Sequence[Tuple[float, float]]):
    """The stretches inside `rounds` (sorted, disjoint) that `busy` (the
    union of device operations) leaves uncovered."""
    idle = []
    for r0, r1 in rounds:
        t = r0
        for a, b in R.intersect(busy, [(r0, r1)]):
            if a > t:
                idle.append((t, a))
            t = b
        if r1 > t:
            idle.append((t, r1))
    return idle


def idle_by_span(idle: Iterable[Tuple[float, float]],
                 spans: Sequence[Span]) -> Dict[str, float]:
    """Split each idle stretch over the innermost span open across each of
    its pieces (the shortest one holding it); time under no span counts
    as ``(none)``."""
    out: Dict[str, float] = {}
    for a, b in idle:
        over = [s for s in spans if s[1] < b and s[2] > a]
        cuts = sorted({a, b} | {t for _, s, e in over for t in (s, e)
                                if a < t < b})
        for u, v in zip(cuts, cuts[1:]):
            mid = (u + v) / 2
            open_ = [(e - s, name) for name, s, e in over if s <= mid < e]
            name = min(open_)[1] if open_ else "(none)"
            out[name] = out.get(name, 0.0) + (v - u)
    return out


def breakdown(pd, op_scopes: Dict[str, Dict[str, str]],
              programs: Sequence[str] = ("paged_decode_round",
                                         "paged_prefill_chunk"),
              top: int = 12) -> dict:
    """The ``programs`` and ``idle`` parts of the result line."""
    by_op, runs = op_device_ns(pd, programs)
    out = {"programs": {}}
    for p in programs:
        if p not in runs:
            continue
        n = len(runs[p])
        # every compiled variant of the program (table widths, chunk
        # buckets); a name two variants give different scopes keeps the
        # first's
        scopes: Dict[str, str] = {}
        for key, m in op_scopes.items():
            if key.split("[")[0] == p:
                for k, v in m.items():
                    scopes.setdefault(k, v)
        ops = by_op.get(p, {})
        heavy = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        out["programs"][p] = {
            "runs": n,
            "module_ms_per_run": sum(runs[p]) / n / 1e6,
            "op_ms_per_run": sum(ops.values()) / n / 1e6,
            "scope_ms_per_run": {k: v / n / 1e6 for k, v in
                                 sorted(by_scope(ops, scopes).items())},
            "part_ms_per_run": {k: v / n / 1e6 for k, v in
                                sorted(by_scope(ops, scopes, 2).items())},
            "top_ops_ms_per_run": [
                [k, scopes.get(k, "unmatched"), v / n / 1e6]
                for k, v in heavy],
        }
    dev_ops, _, rounds = R.events(pd)
    busy = R.union((a, b) for a, b, _ in dev_ops)
    idle = in_round_idle(busy, R.union((a, b) for _, a, b in rounds))
    split = idle_by_span(idle, host_spans(pd))
    total = sum(split.values())
    inner = total - split.get("round", 0.0) - split.get("(none)", 0.0)
    out["idle"] = {
        "in_round_idle_ms": total / 1e6,
        "under_child_span_share": inner / total if total else None,
        "by_span_ms": {k: v / 1e6 for k, v in
                       sorted(split.items(), key=lambda kv: -kv[1])},
    }
    return out


def main(argv=None) -> int:
    from bench import run as bench_run
    from bench.chip import NoChip

    args = bench_run.parse(argv)
    _, cell, conf, _, _ = bench_run.cell_spec(args.workload)
    cfg = json.loads((bench_run.ROOT / conf["file"]).read_text())
    from bench import traffic
    mix = traffic.load_mix(cell["traffic"], bench_run.BENCH / "traffic")
    bench_run.configure_jax()
    from bench import chip
    from bench.stats import CompileLog
    try:
        _, peaks = chip.check(cell["chips"])
    except NoChip as e:
        print(f"[scopes] cannot measure here: {e}", file=sys.stderr)
        return 3
    name = cfg["serve"]["driver"]
    drivers = bench_run.load_module(
        bench_run.BENCH / "drivers" / f"{name}.py", f"bench_driver_{name}")
    drv = drivers.ServeDriver(cfg, mix, args.seed, args.seconds,
                              trace_s=bench_run.TRACE_SECONDS)
    drv.run(T_PROCESS, CompileLog())
    from repro.obs import trace as obs_trace

    spans = bench_run.window_spans(drv)
    reader = bench_run.load_module(
        bench_run.BENCH / "metrics" / "round_host_ms.py", "bench_round_host")
    out = breakdown(R.load(drv.profile_dir), drv.engine.op_scopes())
    drv._tmp.cleanup()
    out.update({
        "output_tok_s": drv.end_to_end().get("output_tok_s"),
        "round_host_ms": reader.read(bench_run.Run(cfg, peaks, drv, spans)),
        "tracer_dropped": obs_trace.get_tracer().dropped,
        "compiles_in_window": [n for _, n, _ in drv.compiles],
    })
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
