"""Serving launcher: batched prefill + decode with KV caches.

Two engines share one jitted, cache-donating decode-step discipline:

  dense - the fixed-batch baseline: one ``[batch, max_len]`` cache, one
          jitted `model.decode_step` reused for every token.
  paged - `repro.serve.PagedServingEngine`: continuous batching over a
          paged KV block pool, ragged prompt lengths, round width coupled
          to the autotuned coroutine depth.

Both report p50/p99 per-token latency alongside the aggregate
`decode_tok_per_s`.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --batch 4 --prompt-len 64 --gen 32 --engine paged
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, token_split
from repro.core import autotune, guard
from repro.core.machine import get_machine
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.obs import trace as obs_trace
from repro.obs.metrics import latency_report
from repro.sharding import NULL_CTX


def make_prompts(cfg, batch, prompt_len, rng):
    front, text = token_split(cfg, prompt_len)
    b = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (batch, text)), jnp.int32),
        "positions": jnp.tile(jnp.arange(text, dtype=jnp.int32), (batch, 1)),
    }
    if cfg.enc_dec:
        b["frames"] = jnp.asarray(rng.normal(size=(batch, front, cfg.d_model)) * 0.02,
                                  jnp.bfloat16).astype(jnp.dtype(cfg.dtype))
    if cfg.vlm:
        b["patches"] = jnp.asarray(rng.normal(size=(batch, front, cfg.d_model)) * 0.02,
                                   jnp.bfloat16).astype(jnp.dtype(cfg.dtype))
    return b, text


def jit_decode_step(model):
    """The one jitted decode step every engine drive loop reuses: the cache
    is donated so each token updates it in place instead of copying."""
    return jax.jit(model.decode_step, donate_argnums=(1,))


def timed_decode_loop(decode, params, cache, tokens, *, steps, make_batch):
    """Drive `steps` decode calls through one jitted step, timing each.

    Returns (tokens_list, final_tokens, per-step latencies in seconds).
    Per-step sync is what makes p50/p99 meaningful; the cost is reported
    inside the latencies themselves rather than hidden.
    """
    out = [tokens]
    lat = []
    tracer = obs_trace.get_tracer()  # fetched once: null no-op when off
    for i in range(steps):
        t0 = time.perf_counter()
        with tracer.span("decode_step", step=i, batch=int(tokens.shape[0])):
            logits, cache = decode(params, cache, make_batch(tokens, i))
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            jax.block_until_ready(tokens)
        dt = time.perf_counter() - t0
        lat.append(dt)
        # always-on numerics policing (DESIGN.md §2.7): the dense loop has
        # no twin to fall back to, so a non-finite step raises under
        # --strict and is counted (substrate.numerics_faults) otherwise
        nerr = guard.scan_output("serve_dense_decode", logits)
        if nerr is not None and guard.strict_mode():
            raise nerr
        if autotune.telemetry_enabled():
            # one "tile" per request token this step; the first observation
            # (jit compile) is dropped by observe_pipeline's warmup skip
            autotune.observe_pipeline("serve_dense_decode", dt,
                                      int(tokens.shape[0]))
        out.append(tokens)
    return out, tokens, lat


def serve(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0,
          greedy: bool = True, ctx=NULL_CTX, layout: str = "default",
          engine: str = "dense", block_size: int = 16,
          num_blocks: int | None = None, prefix_cache: bool = True,
          prefill_chunk: int = 32, deadline_s: float | None = None,
          chaos: int | None = None, strict: bool = False):
    if strict:
        # CI parity lanes: no silent degradation — a substrate fault raises
        guard.set_strict(True)
    if layout == "serving":
        from repro.runtime.layouts import serving_config_overrides
        cfg = cfg.replace(**serving_config_overrides())
        # (rules take effect when ctx carries a mesh; see runtime.layouts)
    if engine == "paged":
        return serve_paged(cfg, batch=batch, prompt_len=prompt_len, gen=gen,
                           seed=seed, ctx=ctx, block_size=block_size,
                           num_blocks=num_blocks, prefix_cache=prefix_cache,
                           prefill_chunk=prefill_chunk, deadline_s=deadline_s,
                           chaos=chaos)
    if deadline_s is not None or chaos is not None:
        raise ValueError("--deadline-s / --chaos need --engine paged (the "
                         "dense baseline has no per-request lifecycle)")
    model = build_model(cfg, ctx)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts, text_len = make_prompts(cfg, batch, prompt_len, rng)

    prefill = jax.jit(lambda p, b: model.prefill(p, b, pad_to=text_len + gen))
    decode = jit_decode_step(model)

    t0 = time.perf_counter()
    cache, logits = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    tokens = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    def make_batch(tokens, i):
        return {"tokens": tokens,
                "positions": jnp.full((batch, 1), text_len + i, jnp.int32)}

    out, tokens, lat = timed_decode_loop(decode, params, cache, tokens,
                                         steps=gen - 1, make_batch=make_batch)
    t_decode = sum(lat)

    generated = jnp.concatenate(out, axis=1)
    stats = {
        "engine": "dense",
        "machine": get_machine().name,
        "generated_shape": tuple(generated.shape),
        "prefill_s": round(t_prefill, 3),
        "decode_s": round(t_decode, 3),
        "decode_tok_per_s": round(batch * (gen - 1) / max(t_decode, 1e-9), 1),
        "sample_tokens": np.asarray(generated[0, :8]).tolist(),
        "substrate": guard.stats(),
    }
    stats.update(latency_report(lat))
    return stats


def serve_paged(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0,
                ctx=NULL_CTX, block_size: int = 16,
                num_blocks: int | None = None, prefix_cache: bool = True,
                prefill_chunk: int = 32, deadline_s: float | None = None,
                chaos: int | None = None):
    """Continuous batching: `batch` requests with ragged prompt lengths
    (4x spread) through a block pool sized to force page reuse. Half the
    requests share a system-prompt prefix so the prefix cache (when on) has
    something to dedup. `deadline_s` bounds each request's wall clock;
    `chaos` seeds a deterministic fault schedule (serve.FaultInjector) so
    the run doubles as a robustness drill — the stats then report how many
    requests degraded (cancelled/failed/stalled) instead of completing."""
    from repro.serve import FaultInjector, PagedServingEngine

    faults = FaultInjector(chaos) if chaos is not None else None
    rng = np.random.default_rng(seed)
    lo = max(1, prompt_len // 4)
    plens = [int(x) for x in rng.integers(lo, prompt_len + 1, batch)]
    plens[int(np.argmax(plens))] = prompt_len  # keep the nominal worst case

    blocks_per_req = -(-(prompt_len + gen) // block_size)
    if num_blocks is None:
        # roughly half the requests resident at once: completions must free
        # pages for later admissions (the continuous-batching regime)
        num_blocks = blocks_per_req * max(2, (batch + 1) // 2)

    system = rng.integers(0, cfg.vocab, max(lo // 2, 1))
    eng = PagedServingEngine(cfg, ctx, block_size=block_size,
                             num_blocks=num_blocks, seed=seed,
                             prefix_cache=prefix_cache,
                             prefill_chunk=prefill_chunk,
                             deadline_s=deadline_s, faults=faults)
    rids = []
    for i, plen in enumerate(plens):
        body = rng.integers(0, cfg.vocab, plen)
        if i % 2 == 0:  # every other request opens with the system prompt
            body[: len(system)] = system[: plen]
        rids.append(eng.submit(body, max_new_tokens=gen))
    stats = eng.run()
    stats["prompt_lens"] = plens
    stats["generated_lens"] = [len(eng.request(r).generated) for r in rids]
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--layout", default="default", choices=["default", "serving"])
    ap.add_argument("--engine", default="dense", choices=["dense", "paged"])
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None)
    ap.add_argument("--prefix-cache", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="share KV pages across common prompt prefixes "
                         "(paged engine; --no-prefix-cache disables)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="tokens per chunked-prefill step (paged engine)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline in seconds; "
                         "expired requests are CANCELLED at the next round "
                         "boundary (paged engine)")
    ap.add_argument("--strict", action="store_true",
                    help="disable substrate degradation: any kernel "
                         "backoff/fallback/parity mismatch raises its typed "
                         "SubstrateError instead (CI parity lanes)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject a deterministic fault schedule (pool "
                         "exhaustion, reclaim refusal, step exceptions, "
                         "latency spikes) seeded by SEED (paged engine)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="export the run's span trace as Chrome trace-event "
                         "JSON (open in https://ui.perfetto.dev)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    stats = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                  gen=args.gen, layout=args.layout, engine=args.engine,
                  block_size=args.block_size, num_blocks=args.num_blocks,
                  prefix_cache=args.prefix_cache,
                  prefill_chunk=args.prefill_chunk,
                  deadline_s=args.deadline_s, chaos=args.chaos,
                  strict=args.strict)
    if args.trace:
        stats["trace"] = obs_trace.get_tracer().export(args.trace)
        stats["trace_events"] = len(obs_trace.get_tracer().events)
    print(json.dumps(stats))
    return stats


if __name__ == "__main__":
    main()
