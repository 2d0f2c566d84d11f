"""Arithmetic of the yardstick: percentiles and compile counting.

`percentile` is a copy of the nearest-rank percentile of the program's
`repro.obs.metrics`, and `CompileLog` of the compile-event listener of
`chip_smoke.py`, kept here so that no change to the program can move how
the benchmark counts.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def percentile(xs: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over all samples; `q` in [0, 1]."""
    if not xs:
        raise ValueError("percentile of no samples")
    ys = sorted(xs)
    return ys[min(int(q * len(ys)), len(ys) - 1)]


class CompileLog:
    """Backend compile events, from JAX's monitoring hook (a persistent
    cache hit records only its retrieval)."""

    def __init__(self):
        from jax import monitoring
        self.events: List[Tuple[float, str, float]] = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            import time
            name = str(kw.get("fun_name", "?"))
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.events.append((time.perf_counter(), name, duration))

    def between(self, t0: float, t1: float) -> List[Tuple[float, str, float]]:
        return [e for e in self.events if t0 <= e[0] < t1]
