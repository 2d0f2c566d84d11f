"""Training launcher.

CPU-scale entry point (full-scale runs go through the same code with the
production mesh): picks an arch (reduced or custom dims), builds the Markov
data task, and runs the fault-tolerant train loop.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --reduced \
      --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import json

from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.runtime.train_loop import train
from repro.sharding import NULL_CTX, ShardingCtx


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU)")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (e.g. ~100M model)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a crash (fault-tolerance demo)")
    ap.add_argument("--mesh", default="none", choices=["none", "mini"])
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    over = {}
    if args.d_model:
        over.update(d_model=args.d_model,
                    n_heads=max(args.d_model // 64, 1),
                    kv_heads=max(args.d_model // 128, 1),
                    d_ff=args.d_model * 4, head_dim=0)
    if args.layers:
        over["n_layers"] = args.layers
    if args.vocab:
        over["vocab"] = args.vocab
    if over:
        cfg = cfg.replace(**over)

    ctx = NULL_CTX
    if args.mesh == "mini":
        from repro.launch.mesh import mesh_by_name
        ctx = ShardingCtx(mesh=mesh_by_name("mini"))

    model = build_model(cfg, ctx)
    print(f"arch={cfg.name} params={model.n_params()/1e6:.1f}M "
          f"layers={cfg.n_layers} d_model={cfg.d_model}")

    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
    report = train(
        model, steps=args.steps, data_cfg=data_cfg,
        opt=AdamWConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(args.steps // 20, 5)),
        accum=args.accum, compress_grads=args.compress_grads,
        ckpt_dir=args.ckpt_dir, fail_at_step=args.fail_at,
    )
    first = min(report.losses)
    last = max(report.losses)
    print(json.dumps({
        "steps": report.steps,
        "loss_first": report.losses[first],
        "loss_last": report.losses[last],
        "resumed_from": report.resumed_from,
        "stragglers": report.straggler_steps,
        "wall_s": round(report.wall_s, 1),
    }))
    return report


if __name__ == "__main__":
    main()
