#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, and the rate sweep.

  python3 bench/calibrate.py control --workload <cell> --seeds 1,2,3 --seconds 51 \
      [--config <name>] [--fault <kind>]
  python3 bench/calibrate.py sweep --workload <cell> --rates 0.1,0.2 --seconds 30

``control``: for each seed, one run of the cell's traffic at the cell's own
load and size (a shorter window), then the numbers `bench/check.py`
compares, of the program against the float32 reference (the lower
readings) and of the fp8 control put in the program's place (the upper
readings), and each side's verdict under the configuration's limits as
`bench/run.py` judges: `correct` (the program's, has to be true) and
`control_correct` (has to be false). Beside them, the share of each
side's gaps above a few sizes, to choose a configuration's `wide_gap` by.
One process serves every seed, so the programs compile once.
``--config`` serves another configuration (`bench/configs/<name>.json`)
under the cell's traffic, for a file that is in no cell yet. ``--fault``
serves the program with its decode round broken (`bench/faults.py`), so
the program's side reads the fault: `correct` has to come out false.

``sweep``: for each rate, one window of the mix with its rate replaced;
prints requests due, failures, the backlog at the window's open and close,
and the tails. The highest rate whose backlog did not grow, with no
failure, is the rate the cell runs at four fifths of.

Both print one JSON object per seed or rate on stdout. They need a TPU, as
`bench/run.py` does; the benchmark's own runs never call them.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run  # noqa: E402


SIZES = (0.0, 0.01, 0.02, 0.05, 0.1, 0.2)


def shares_above(g: np.ndarray) -> dict:
    """The share of one side's gaps above each of `SIZES`."""
    return {str(t): float(np.mean(g > t)) if g.size else None for t in SIZES}


def one(cfg, family, mix, seed, seconds, compile_log, *, control: bool):
    from bench import check
    from bench.drivers.serve import ServeDriver
    from repro.core import autotune

    # a fresh process starts with no transfer samples; so does each engine
    # here, or the solved round width drifts from the cell's
    autotune.clear_samples()
    drv = ServeDriver(cfg, family, mix, seed, seconds)
    drv.run(time.perf_counter(), compile_log)
    out = {"seed": seed, **drv.end_to_end()}
    out["attempted"], out["failed"] = drv.counts()
    out["backlog_open_close"] = drv.backlog
    out["round_width"] = drv.engine.round_width
    drv.free()
    if control:
        sample = check.sample(drv.finished(), seed, cfg["check"]["requests"])
        comp = check.compare(family, cfg, seed, sample,
                             pad_to=mix["context_cap"], control=True)
        out["share_above"] = shares_above(comp.pop("gaps"))
        out["control_share_above"] = shares_above(comp.pop("control_gaps"))
        out.update(comp)
        # the run's own verdict, and the control's under the same limits
        out["correct"], _ = check.judge(cfg, comp, out["failed"])
        out["control_correct"], _ = check.judge(cfg, comp, 0, prefix="control_")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("control", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--config", default=None,
                    help="serve bench/configs/<name>.json in place of the cell's")
    ap.add_argument("--fault", default=None,
                    help="break the decode round (bench/faults.py), e.g. one:256")
    ap.add_argument("--lead", type=float, default=None,
                    help="lead-in seconds before the window (default: the mix's)")
    args = ap.parse_args(argv)

    _, cell, conf, _, _ = bench_run.cell_spec(args.workload)
    path = (bench_run.BENCH / "configs" / f"{args.config}.json" if args.config
            else bench_run.ROOT / conf["file"])
    cfg = json.loads(path.read_text())
    from bench import chip, traffic
    mix = traffic.load_mix(cell["traffic"])
    if args.lead is not None:
        mix["lead_s"] = args.lead
    bench_run.configure_jax()
    family = bench_run.load_family(cfg)
    chip.check(cell["chips"])
    if args.fault:
        from bench import faults
        faults.plant(args.fault)
    from bench.stats import CompileLog
    log = CompileLog()
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.mode == "control":
        for seed in seeds:
            print(json.dumps(one(cfg, family, mix, seed, args.seconds, log,
                                 control=True)), flush=True)
        return 0
    for rate in (float(r) for r in args.rates.split(",")):
        swept = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        out = one(cfg, family, swept, seeds[0], args.seconds, log, control=False)
        print(json.dumps({"rate_per_s": rate, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
