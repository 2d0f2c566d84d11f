"""The chip a run stands on: it has to be a TPU the peaks table knows.

There is no CPU fallback: a run that finds no TPU, fewer chips than its
cell asks for, or a `device_kind` missing from `peaks.json` stops before it
measures anything.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """The run cannot measure here."""


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {PEAKS.name} "
                     f"(known: {sorted(table)})")
    return table[kind]


def check(chips: int):
    """(devices, peaks) for a run that needs `chips` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips], peaks_for(devices[0].device_kind)


def describe(devices) -> dict:
    """The `device` object of a result line (memory added by the caller)."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}
