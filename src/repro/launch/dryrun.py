import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (device count locks at
# first init). REPRO_DRYRUN_DEVICES overrides for mini/CI runs.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import roofline
from repro.core.machine import get_machine
from repro.configs import (
    ALL_ARCH_NAMES,
    ALL_SHAPE_NAMES,
    SHAPES,
    batch_specs,
    cell_supported,
    decode_batch_specs,
    get_config,
)
from repro.launch.mesh import mesh_by_name
from repro.models import build_model
from repro.models import params as pm
from repro.optim import AdamWConfig
from repro.runtime.steps import (
    abstract_state,
    batch_shardings,
    make_decode_step,
    make_prefill_step,
    make_train_step,
    state_shardings,
)
from repro.sharding import ShardingCtx


def _mem_dict(ma):
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _build_step(cfg, shape, mesh, rules=None, accum=1):
    """(fn, args, in_shardings, out_shardings) for one cell config."""
    ctx = ShardingCtx(mesh=mesh, rules=rules)
    model = build_model(cfg, ctx)
    kind = shape.kind
    if kind == "train":
        fn = make_train_step(model, AdamWConfig(), accum=accum)
        bspecs = batch_specs(cfg, shape)
        args = (abstract_state(model), bspecs)
        in_sh = (state_shardings(model), batch_shardings(ctx, bspecs))
        out_sh = (state_shardings(model), None)
    elif kind == "prefill":
        fn = make_prefill_step(model)
        bspecs = batch_specs(cfg, shape)
        args = (model.abstract_params(), bspecs)
        in_sh = (model.param_shardings(), batch_shardings(ctx, bspecs))
        out_sh = (model.cache_shardings(shape), None)
    else:  # decode
        fn = make_decode_step(model)
        bspecs = decode_batch_specs(cfg, shape)
        args = (model.abstract_params(), model.abstract_cache(shape), bspecs)
        in_sh = (model.param_shardings(), model.cache_shardings(shape),
                 batch_shardings(ctx, bspecs))
        out_sh = (None, model.cache_shardings(shape))
    return model, fn, args, in_sh, out_sh


def _compile(cfg, shape, mesh, rules=None, accum=1):
    model, fn, args, in_sh, out_sh = _build_step(cfg, shape, mesh, rules, accum)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    return {
        "model": model,
        "lowered": lowered,
        "compiled": compiled,
        "t_lower": t_lower,
        "t_compile": t_compile,
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "hbm": roofline.hbm_bytes(txt),
        "memory": _mem_dict(compiled.memory_analysis()),
        "collectives": roofline.collective_bytes(txt),
    }


def _depth_override(cfg, n: int):
    kw = {"n_layers": n, "scan_layers": False}
    if cfg.enc_dec:
        kw["n_enc_layers"] = n
    return cfg.replace(**kw)


def _extrapolate(c2: dict, c6: dict, L: int):
    """Linear-in-depth reconstruction: cost(L) = c2 + (L-2)/(6-2) * (c6-c2)."""
    f = (L - 2) / 4.0

    def lin(a, b):
        return max(a + f * (b - a), 0.0)

    coll_types = set(c2["collectives"]) | set(c6["collectives"])
    coll = {
        k: int(lin(c2["collectives"].get(k, 0), c6["collectives"].get(k, 0)))
        for k in coll_types
    }
    return {
        "flops": lin(c2["flops"], c6["flops"]),
        "bytes": lin(c2["bytes"], c6["bytes"]),
        "hbm": lin(c2["hbm"], c6["hbm"]),
        "collectives": coll,
    }


DEPTHS = (2, 6)


def run_cell(arch: str, shape_name: str, mesh_name: str, *, out_dir=None,
             overrides=None, rules=None, accum=1, verbose=True,
             full_unroll=False):
    """Lower + compile one (arch x shape x mesh) cell; return roofline record.

    Methodology (DESIGN.md §3.2): the FULL model is compiled with
    scan-over-layers — that run proves the sharding lowers and gives the real
    per-device memory analysis. Exact FLOPs / bytes / collective-bytes come
    from unrolled depth-2 and depth-6 compiles extrapolated linearly in L
    (XLA cost analysis counts loop bodies once, so scanned counts are wrong
    and full-depth unrolled compiles are prohibitively slow on one CPU core;
    `full_unroll=True` compiles the real thing for cross-validation).
    """
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    ok, why = cell_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        rec.update(status="skipped", reason=why)
        if out_dir is not None:
            out_dir = Path(out_dir)
            out_dir.mkdir(parents=True, exist_ok=True)
            tag = f"{arch}__{shape_name}__{mesh_name}"
            (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
        return rec

    if overrides:
        cfg = cfg.replace(**overrides)
    mesh = mesh_by_name(mesh_name)
    n_chips = mesh.devices.size
    kind = shape.kind
    L = cfg.n_layers

    # --- 1) full model, scanned: proves lowering + real memory analysis
    full = _compile(cfg.replace(scan_layers=True), shape, mesh, rules, accum)
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] full(scan) "
              f"lower={full['t_lower']:.1f}s compile={full['t_compile']:.1f}s")
        print("  memory_analysis:", full["memory"])

    # --- 2) depth-2 / depth-6 unrolled: exact per-layer costs
    if full_unroll:
        cx = _compile(cfg.replace(scan_layers=False), shape, mesh, rules)
        est = {"flops": cx["flops"], "bytes": cx["bytes"], "hbm": cx["hbm"],
               "collectives": cx["collectives"]}
        depth_info = {"mode": "full_unroll", "t_compile": cx["t_compile"]}
    else:
        c2 = _compile(_depth_override(cfg, DEPTHS[0]), shape, mesh, rules)
        c6 = _compile(_depth_override(cfg, DEPTHS[1]), shape, mesh, rules)
        est = _extrapolate(c2, c6, L)
        depth_info = {
            "mode": f"extrapolated_{DEPTHS[0]}_{DEPTHS[1]}",
            "d2": {"flops": c2["flops"], "bytes": c2["bytes"]},
            "d6": {"flops": c6["flops"], "bytes": c6["bytes"]},
        }

    # the roofline terms read the SAME machine model the depth solver uses
    # (core.machine's active profile; dial with REPRO_MACHINE)
    machine = get_machine()
    t = roofline.terms(est["flops"], est["bytes"], est["collectives"],
                       machine=machine)
    mflops = roofline.model_flops(cfg, shape, kind)

    rec.update(
        status="ok",
        machine=machine.name,
        kind=kind,
        chips=int(n_chips),
        compile_s=round(full["t_compile"], 2),
        memory=full["memory"],
        flops_scanned_per_chip=full["flops"],
        hlo_flops_per_chip=est["flops"],
        hlo_bytes_per_chip=est["bytes"],
        hbm_bytes_per_chip=est["hbm"],
        memory_hbm_s=est["hbm"] / machine.hbm_bw,
        collective_bytes=est["collectives"],
        terms=t,
        dominant=roofline.dominant(t),
        model_flops_total=mflops,
        model_flops_per_chip=mflops / n_chips,
        useful_flops_ratio=(mflops / n_chips) / est["flops"] if est["flops"] else 0.0,
        depth_info=depth_info,
    )
    if verbose:
        print("  est: flops=%.3e bytes=%.3e hbm=%.3e coll=%s"
              % (est["flops"], est["bytes"], est["hbm"], est["collectives"]))
        print("  terms: compute=%.3es memory=%.3es collective=%.3es dominant=%s"
              % (t["compute_s"], t["memory_s"], t["collective_s"], rec["dominant"]))
        print("  useful_flops_ratio=%.3f" % rec["useful_flops_ratio"])

    if out_dir is not None:
        out_dir = Path(out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        tag = f"{arch}__{shape_name}__{mesh_name}"
        (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape id or 'all'")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "mini", "both"])
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. remat=False)")
    ap.add_argument("--rule", action="append", default=[],
                    help="sharding rule override logical=mesh_axis|none")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (train cells)")
    ap.add_argument("--full-unroll", action="store_true",
                    help="exact full-depth unrolled cost compile (slow)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = json.loads(v) if v not in ("True", "False") else (v == "True")
    rules = {}
    for kv in args.rule:
        k, v = kv.split("=", 1)
        rules[k] = None if v in ("none", "None") else v

    archs = list(ALL_ARCH_NAMES) if args.arch == "all" else [args.arch]
    shapes = list(ALL_SHAPE_NAMES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                try:
                    rec = run_cell(arch, shape, mesh, out_dir=args.out,
                                   overrides=overrides or None,
                                   rules=rules or None, accum=args.accum,
                                   full_unroll=args.full_unroll)
                    if rec["status"] == "skipped":
                        print(f"[{arch} x {shape} x {mesh}] SKIPPED: {rec['reason']}")
                except Exception as e:  # record and continue
                    traceback.print_exc()
                    failures.append((arch, shape, mesh, repr(e)))
                    Path(args.out).mkdir(parents=True, exist_ok=True)
                    tag = f"{arch}__{shape}__{mesh}"
                    (Path(args.out) / f"{tag}.json").write_text(json.dumps(
                        {"arch": arch, "shape": shape, "mesh": mesh,
                         "status": "error", "error": repr(e)}, indent=1))
    if failures:
        print("FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("dry-run complete: all cells OK")


if __name__ == "__main__":
    main()
