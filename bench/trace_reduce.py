"""From a profiler trace to the numbers the benchmark reports.

Input: the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData` (nothing else is needed). The device planes
(``/device:...``) hold the programs the chip ran (line ``XLA Modules``)
and the operations inside them (line ``XLA Ops``); the host planes hold the
benchmark's ``round`` annotations (`jax.profiler.TraceAnnotation`), one
around each `step_round`, numbered by the argument ``n``.

Output (`reduce`):

* ``busy_ns``: union of the device's operation intervals over the trace;
* ``round_ns`` / ``busy_in_rounds_ns``: union of the round annotations, and
  the device-busy time inside them; their ratio is the in-round busy share
  (time spent waiting for arrivals is outside every round);
* ``programs``: device time per program name, whole trace and per round;
* ``top_ops``: the operations that took the most device time;
* ``gaps``: the longest device-idle stretches inside rounds, each named by
  the host span open at its middle (host spans are given in the host's
  clock and placed on the trace's by the round annotations).

The device planes' clock and the host's differ by about a millisecond in
the traces seen on a v5e, so a program is given to the round whose start is
the latest at or before its own start plus `SKEW_NS`.

Importing this module touches no accelerator.
"""
from __future__ import annotations

import bisect
import glob
import os
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
SKEW_NS = 2e6
CONTROL_FLOW = ("%while", "%conditional", "%call")


def find_xplane(directory: str) -> str:
    hits = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(hits, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return ProfileData.from_file(path)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def events(pd):
    """(device ops, device modules, host round annotations) of a trace:
    ops and modules as (start_ns, end_ns, name); rounds as
    (n, start_ns, end_ns)."""
    ops, modules, rounds = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            op_line = lines.get("XLA Ops")
            for ev in (op_line.events if op_line is not None else ()):
                ops.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
            mod_line = lines.get("XLA Modules")
            for ev in (mod_line.events if mod_line is not None else ()):
                modules.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "round":
                        n = _stat(ev, "n")
                        rounds.append((int(n) if n is not None else -1,
                                       ev.start_ns, ev.start_ns + ev.duration_ns))
    ops.sort()
    modules.sort()
    rounds.sort(key=lambda r: r[1])
    return ops, modules, rounds


def _program(module_name: str, programs: Sequence[str]) -> Optional[str]:
    for p in programs:
        if p in module_name:
            return p
    return None


def _short(op: str) -> str:
    """``%copy.65 = bf16[1,3601,16,8,64]{...} copy(...)`` -> ``copy.65
    bf16[1,3601,16,8,64]``."""
    lhs, _, rhs = op.partition(" = ")
    return f"{lhs.lstrip('%')} {rhs.split('{')[0]}".strip()


def clock_offset_ns(rounds, host_round_starts: Dict[int, float]) -> Optional[float]:
    """Trace ns minus host perf_counter seconds * 1e9, from rounds seen on
    both sides (the median, so one late annotation does not move it)."""
    d = [start - host_round_starts[n] * 1e9 for n, start, _ in rounds
         if n in host_round_starts]
    return statistics.median(d) if d else None


def reduce(pd, programs: Sequence[str] = ("paged_decode_round",
                                          "paged_prefill_chunk"),
           host_spans: Sequence[Tuple[str, float, float]] = (),
           host_round_starts: Optional[Dict[int, float]] = None,
           top: int = 10) -> dict:
    """Reduce a trace; `host_spans` are (name, start_s, end_s) on the host's
    perf_counter clock, `host_round_starts` maps round n to its start on
    that clock."""
    ops, modules, rounds = events(pd)
    busy = union((a, b) for a, b, _ in ops)
    in_round = union((a, b) for _, a, b in rounds)
    busy_in = intersect(busy, in_round)

    # device time per program, whole trace and per round
    mod_starts = [m[0] for m in modules]
    round_starts = [r[1] for r in rounds]
    prog_total: Dict[str, float] = {}
    prog_round: Dict[str, Dict[int, float]] = {}
    for a, b, name in modules:
        p = _program(name, programs)
        if p is None:
            continue
        prog_total[p] = prog_total.get(p, 0.0) + (b - a)
        i = bisect.bisect_right(round_starts, a + SKEW_NS) - 1
        if i >= 0 and a < rounds[i][2]:
            n = rounds[i][0]
            prog_round.setdefault(p, {})
            prog_round[p][n] = prog_round[p].get(n, 0.0) + (b - a)

    # operations by (program, op) name; loops and calls wrap other ops and
    # would count their time twice
    by_op: Dict[str, float] = {}
    for a, b, name in ops:
        if name.startswith(CONTROL_FLOW):
            continue
        i = bisect.bisect_right(mod_starts, a) - 1
        mod = modules[i][2] if i >= 0 and modules[i][1] >= b else "?"
        key = f"{_program(mod, programs) or mod}/{_short(name)}"
        by_op[key] = by_op.get(key, 0.0) + (b - a)
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    # idle stretches inside rounds, named by the host span open at each
    idle = []
    for r0, r1 in in_round:
        t = r0
        for a, b in intersect(busy, [(r0, r1)]):
            if a > t:
                idle.append((t, a))
            t = b
        if r1 > t:
            idle.append((t, r1))
    idle.sort(key=lambda g: -(g[1] - g[0]))
    off = (clock_offset_ns(rounds, host_round_starts)
           if host_round_starts else None)
    spans = []
    if off is not None:
        spans = [(name, s * 1e9 + off, e * 1e9 + off) for name, s, e in host_spans]
    gaps = []
    for a, b in idle[:top]:
        mid = (a + b) / 2
        open_ = [(e - s, name) for name, s, e in spans if s <= mid < e]
        gaps.append((min(open_)[1] if open_ else "round (no span open)",
                     (b - a) / 1e9))
    return {
        "busy_ns": length(busy),
        "round_ns": length(in_round),
        "busy_in_rounds_ns": length(busy_in),
        "rounds": rounds,
        "programs": prog_total,
        "program_rounds": prog_round,
        "top_ops": [(k, v / 1e9) for k, v in top_ops],
        "gaps": gaps,
        "n_ops": len(ops),
        "n_modules": len(modules),
    }


def describe(pd) -> List[str]:
    """Plane and line names with event counts: what a trace holds."""
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            out.append(f"{plane.name} | {line.name} | {len(list(line.events))}")
    return out
