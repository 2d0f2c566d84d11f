#!/usr/bin/env python3
"""Bring-up smoke on TPU: the Pallas kernels, the paged server and, with
``--chips 4``, sharded MoE training.

  python chip_smoke.py            # one chip: kernel phase, then serve phase
  python chip_smoke.py --chips 4  # four chips: the sharded-training phase only

Kernel phase: every Pallas kernel family runs once through its public op
under the strict guard and is compared with its ``ref.py`` oracle at the
guard's tolerances. Each lowered program must hold a ``tpu_custom_call``,
and the guard must count no fallback, backoff or parity mismatch.

Serve phase: ``repro.launch.serve.serve(engine="paged", layout="serving",
strict=True)`` on granite-3-2b at its published widths, with random bf16
weights from seed 0: 8 requests with prompts up to 512 tokens, 32 new
tokens each. Every request must complete, with no step fault, some
prefix-cache hits and finite logits, and the compiled decode step must hold
a ``tpu_custom_call`` (its attention is the paged kernel).

Four chips: a few steps of granite-moe-1b-a400m (published widths, cut to
4 layers) through ``launch/train.py --mesh mini`` on a 2x2 mesh; the large
parameter and optimizer leaves must be sharded, and the first step's loss
must match the same step run unsharded on one chip.

What this prints comes from one smoke run, not a benchmark. The last line
of stdout is ``{"ok": true, "device": {...}}``; without a TPU, or on any
failed check, the script exits non-zero before printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PALLAS_CALL = "tpu_custom_call"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# the serve phase: granite-3-2b's published widths, one chip's pool
SERVE = dict(arch="granite-3-2b", batch=8, prompt_len=512, gen=32,
             block_size=16, num_blocks=1024)
# the four-chip phase: granite-moe-1b-a400m cut to 4 layers, 2x2 mesh
TRAIN = dict(arch="granite-moe-1b-a400m", layers=4, steps=3, batch=8,
             seq=256)
LARGE_LEAF = 1 << 20   # elements: leaves this big must be sharded


class SmokeFailure(Exception):
    """A check failed: the script exits non-zero without its last line."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print("[smoke]", *parts, flush=True)


class CompileLog:
    """Backend compile seconds per program, from JAX's monitoring events
    (a persistent-cache hit records only the retrieval time)."""

    def __init__(self):
        from jax import monitoring
        self.events = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            name = str(kw.get("fun_name", "?"))       # "jit(<function>)"
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.events.append((name, duration))

    def seconds(self, since: int = 0) -> float:
        return sum(s for _, s in self.events[since:])


# ------------------------------------------------------------- kernel phase


def kernel_cases():
    """(name, op, args, oracle) per kernel family at the widths it serves."""
    from repro.kernels.coro_gather.ops import coro_gather
    from repro.kernels.coro_gather.ref import gather_ref
    from repro.kernels.coro_scatter_add.ops import coro_scatter_add
    from repro.kernels.coro_scatter_add.ref import scatter_add_ref
    from repro.kernels.decode_attention.ops import (
        decode_attention,
        paged_decode_attention,
    )
    from repro.kernels.decode_attention.ref import (
        decode_attention_ref,
        paged_decode_attention_ref,
    )
    from repro.kernels.moe_gmm.ops import moe_gmm
    from repro.kernels.moe_gmm.ref import gmm_ref
    from repro.kernels.ssd_scan.ops import ssd
    from repro.kernels.ssd_scan.ref import ssd_ref
    from repro.kernels.stream_copy.ops import stream_triad
    from repro.kernels.stream_copy.ref import triad_ref

    rng = np.random.default_rng(0)

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    cases = []
    bf16 = jnp.bfloat16
    # decode attention at granite-3-2b (32/8 heads x 64) and yi-6b (32/4 x 128)
    for arch, kh, d in (("granite-3-2b", 8, 64), ("yi-6b", 4, 128)):
        # the stacked, lane-folded pools the engine serves; layer 2 is read
        h, blk, nb, b, m, n_layers, layer = 32, 16, 512, 8, 32, 3, 2
        q = normal((b, h, d), bf16)
        kp = normal((n_layers, nb, blk, kh * d), bf16)
        vp = normal((n_layers, nb, blk, kh * d), bf16)
        tables = (rng.permutation(nb - 1)[:b * m] + 1).reshape(b, m)
        lens = rng.integers(1, m * blk + 1, b)
        cases.append((
            f"paged_decode_attention {arch} q{tuple(q.shape)} pools{tuple(kp.shape)}",
            lambda q, k, v, t, n, layer=layer:
                paged_decode_attention(q, k, v, layer, t, n),
            (q, kp, vp, jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32)),
            lambda q=q, kp=kp, vp=vp, t=tables, n=lens, layer=layer, kh=kh, d=d:
                paged_decode_attention_ref(
                    q, kp[layer].reshape(nb, blk, kh, d),
                    vp[layer].reshape(nb, blk, kh, d), t, n)))
        kc, vc = normal((4, 1024, kh, d), bf16), normal((4, 1024, kh, d), bf16)
        qd = normal((4, h, d), bf16)
        cases.append((
            f"decode_attention {arch} q{tuple(qd.shape)} cache{tuple(kc.shape)}",
            lambda q, k, v: decode_attention(q, k, v, 777),
            (qd, kc, vc),
            lambda q=qd, k=kc, v=vc: decode_attention_ref(q, k, v, 777)))
    # granite-moe-1b-a400m experts: 32 x [1024 -> 512]
    tok, w = normal((32, 128, 1024), bf16), normal((32, 1024, 512), bf16, 0.03)
    cases.append(("moe_gmm granite-moe-1b-a400m", moe_gmm, (tok, w),
                  lambda: gmm_ref(tok, w)))
    # mamba2-130m: 24 heads x 64, state 128, chunk 256
    x = normal((2, 1024, 24, 64))
    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((2, 1024, 24)))) * 0.1,
                     jnp.float32)
    a = -jnp.asarray(np.exp(rng.uniform(0, 1, 24)), jnp.float32)
    bm, cm = normal((2, 1024, 128), scale=0.3), normal((2, 1024, 128), scale=0.3)
    cases.append(("ssd mamba2-130m x(2,1024,24,64) chunk 256",
                  lambda *ar: ssd(*ar, chunk=256), (x, dt, a, bm, cm),
                  lambda: ssd_ref(x, dt, a, bm, cm)))
    table = normal((1 << 20, 128))
    idx = jnp.asarray(rng.integers(0, 1 << 20, 4096), jnp.int32)
    cases.append(("coro_gather table(1048576,128) idx 4096", coro_gather,
                  (table, idx), lambda: gather_ref(table, idx)))
    stable = normal((65536, 128))
    sidx = rng.integers(0, 65536, 4096)       # duplicates merge in the plan
    upd = normal((4096, 128))
    # the dedup plan runs on the host, so indices and updates are concrete
    cases.append(("coro_scatter_add table(65536,128) updates 4096",
                  lambda t: coro_scatter_add(t, sidx, upd), (stable,),
                  lambda: scatter_add_ref(stable, jnp.asarray(sidx), upd)))
    tb, tc = normal((65536, 1024)), normal((65536, 1024))
    cases.append(("stream_triad (65536,1024) f32 rows 512",
                  lambda b_, c_: stream_triad(b_, c_, 3.0, rows=512), (tb, tc),
                  lambda: triad_ref(tb, tc, 3.0)))
    return cases


def kernel_phase(log: CompileLog) -> None:
    from repro.core import guard

    guard.set_strict(True)
    mark = len(log.events)
    for name, op, args, oracle in kernel_cases():
        t0 = time.perf_counter()
        out = jax.block_until_ready(op(*args))
        first_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):   # an exact oracle
            ref = oracle()
        has_kernel = PALLAS_CALL in jax.jit(op).lower(*args).as_text()
        diffs = [float(jnp.max(jnp.abs(jnp.asarray(o, jnp.float32)
                                       - jnp.asarray(r, jnp.float32))))
                 for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref))]
        match = guard.parity_matches(out, ref)
        say(f"kernel {name}: oracle {'match' if match else 'MISMATCH'} "
            f"max_abs_err={max(diffs)} {PALLAS_CALL}={has_kernel} "
            f"first_call_s={first_s}")
        check(match, f"{name}: output differs from its ref.py oracle")
        check(has_kernel, f"{name}: lowered program holds no {PALLAS_CALL}")
    sub = guard.stats()
    say(f"kernel guard: guarded_calls={sub['guarded_calls']} "
        f"fallbacks={sub['fallbacks']} backoffs={sub['backoffs']} "
        f"parity_mismatches={sub['parity_mismatches']} "
        f"compile_s={log.seconds(mark)}")
    for key in ("fallbacks", "backoffs", "parity_mismatches", "numerics_faults"):
        check(sub[key] == 0, f"guard counted {key}={sub[key]} in the kernel phase")


# -------------------------------------------------------------- serve phase


def _programs(ir_dir: Path):
    """{program: holds a Pallas kernel} from the modules JAX dumped."""
    out = {}
    for f in sorted(ir_dir.glob("*_compile.mlir")):
        name = f.name.split("_jit_", 1)[-1][:-len("_compile.mlir")]
        out[name] = out.get(name, False) or PALLAS_CALL in f.read_text()
    return out


def serve_phase(log: CompileLog) -> None:
    from repro.configs import get_config
    from repro.launch.serve import serve

    cfg = get_config(SERVE["arch"])
    say(f"serve {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"bf16 params, {SERVE['batch']} requests x {SERVE['gen']} new tokens")
    mark = len(log.events)
    with tempfile.TemporaryDirectory() as ir_dir:
        jax.config.update("jax_dump_ir_to", ir_dir)
        try:
            t0 = time.perf_counter()
            stats = serve(cfg, batch=SERVE["batch"],
                          prompt_len=SERVE["prompt_len"], gen=SERVE["gen"],
                          engine="paged", layout="serving",
                          block_size=SERVE["block_size"],
                          num_blocks=SERVE["num_blocks"], strict=True)
            wall_s = time.perf_counter() - t0
        finally:
            jax.config.update("jax_dump_ir_to", "")
        programs = _programs(Path(ir_dir))

    compile_s = {}
    for fun, s in log.events[mark:]:
        compile_s[fun] = compile_s.get(fun, 0.0) + s
    for name in sorted(programs):
        say(f"compiled program {name}: pallas_kernel={programs[name]} "
            f"compile_s={compile_s.get(name, 0.0)}")
    check("paged_decode_round" in programs, "no compiled paged decode step")
    check(programs["paged_decode_round"],
          f"the decode step holds no {PALLAS_CALL}: its attention is not the kernel")
    peak = jax.devices()[0].memory_stats() or {}
    say(f"serve (one smoke run, not a benchmark): wall_s={wall_s} "
        f"compile_s={log.seconds(mark)} "
        f"peak_bytes_in_use={peak.get('peak_bytes_in_use', 'not reported')}")
    say(f"serve (one smoke run, not a benchmark): ttft_p50_ms={stats['ttft_p50_ms']} "
        f"tbt_p50_ms={stats['tbt_p50_ms']} completed={stats['completed']} "
        f"generated={stats['generated_lens']} prompts={stats['prompt_lens']} "
        f"prefix_hits={stats['prefix_hits']} step_faults={stats['step_faults']}")
    sub = stats["substrate"]
    check(stats["completed"] == SERVE["batch"],
          f"{stats['completed']}/{SERVE['batch']} requests complete")
    check(stats["generated_lens"] == [SERVE["gen"]] * SERVE["batch"],
          f"generated lengths {stats['generated_lens']}")
    check(stats["step_faults"] == 0, f"step_faults={stats['step_faults']}")
    check(stats["prefix_hits"] > 0, "no prefix-cache hit")
    # strict mode raises on a non-finite prefill chunk or decode row; the
    # counter confirms none was absorbed
    check(sub["numerics_faults"] == 0, f"numerics_faults={sub['numerics_faults']}")
    check(sub["fallbacks"] == 0, f"guard fallbacks={sub['fallbacks']}")


# ----------------------------------------------------------- four-chip phase


def train_phase() -> None:
    from repro.configs import get_config
    from repro.core import guard
    from repro.data.pipeline import DataConfig, MarkovTask
    from repro.launch import train as train_launch
    from repro.models import build_model

    full = get_config(TRAIN["arch"])
    say(f"train {full.name}: published widths, cut from {full.n_layers} to "
        f"{TRAIN['layers']} layers; mesh mini over {len(jax.devices())} chips")
    report = train_launch.main([
        "--arch", TRAIN["arch"], "--layers", str(TRAIN["layers"]),
        "--mesh", "mini", "--steps", str(TRAIN["steps"]),
        "--batch", str(TRAIN["batch"]), "--seq", str(TRAIN["seq"])])

    for part in ("params", "mu", "nu"):
        leaves = jax.tree_util.tree_leaves_with_path(report.state[part])
        for path, leaf in leaves:
            if leaf.size < LARGE_LEAF:
                continue
            n_dev = len(leaf.sharding.device_set)
            shard = leaf.sharding.shard_shape(leaf.shape)
            say(f"{part}{jax.tree_util.keystr(path)} {tuple(leaf.shape)}: "
                f"device_set={n_dev} shard={tuple(shard)}")
            check(n_dev == 4 and not leaf.sharding.is_fully_replicated,
                  f"{part}{jax.tree_util.keystr(path)} is not sharded")

    cfg = full.replace(n_layers=TRAIN["layers"])
    model = build_model(cfg)
    batch = MarkovTask(DataConfig(vocab=cfg.vocab, seq_len=TRAIN["seq"],
                                  global_batch=TRAIN["batch"])).batch_for_step(0)
    with jax.default_device(jax.devices()[0]):
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        one_chip = float(jax.jit(lambda p, b: model.loss(p, b)[0])(params, batch))
    sharded = report.losses[0]
    match = guard.parity_matches(jnp.asarray(sharded, jnp.bfloat16),
                                 jnp.asarray(one_chip, jnp.float32))
    say(f"first-step loss: sharded 2x2={sharded} one chip={one_chip} "
        f"diff={abs(sharded - one_chip)} within bf16 tolerance={match}")
    check(match, "sharded first-step loss differs from the one-chip step")


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training phase on a 2x2 mesh")
    args = ap.parse_args(argv)

    from repro.core.machine import check_device
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU: jax runs on {dev.platform!r}")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} chips, found {len(devices)}")
    machine = check_device(dev.device_kind)
    say(f"device {dev.device_kind} x{len(devices)}; machine profile "
        f"{machine.name}; compile cache {cache_dir}")
    log = CompileLog()
    if args.chips == 4:
        train_phase()
    else:
        kernel_phase(log)
        serve_phase(log)
    say(f"total compile_s={log.seconds()} programs={len(log.events)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
