"""Shared-prefix KV cache + chunked prefill (ISSUE-7): radix index unit
tests, copy-on-write lifecycle, jit-cache bounds, budgeted-round fairness,
and the end-to-end acceptance scenario (shared prefixes dedup physical
pages without changing a single emitted token)."""
import math

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import autotune
from repro.models import build_model
from repro.serve import (
    ContinuousBatchingScheduler,
    KVPager,
    PagedServingEngine,
    PrefixCache,
    Request,
    bucket_len,
)

# ------------------------------------------------------------- radix index


def _pager_cache(num_blocks=16, blk=4):
    pager = KVPager(num_blocks=num_blocks, block_size=blk)
    return pager, PrefixCache(pager)


def test_prefix_match_walks_full_blocks():
    pager, cache = _pager_cache()
    table = pager.alloc(0, 12)  # 3 blocks
    toks = list(range(100, 112))
    assert cache.insert(toks, table) == 3
    m = cache.match(toks + [7, 8])
    assert m.hit and m.n_tokens == 12 and m.blocks == table
    # a diverging prompt matches only the common full blocks
    m = cache.match(toks[:8] + [1, 2, 3, 4])
    assert m.n_tokens == 8 and m.blocks == table[:2]
    assert cache.match([9, 9, 9, 9]).hit is False
    pager.check_invariants(extra_refs=cache.block_refs())


def test_prefix_match_shares_partial_block_on_lcp():
    """Divergence inside a block still shares that page (n_tokens lands
    mid-block) — the requester CoW-forks before writing its own rows."""
    pager, cache = _pager_cache()
    table = pager.alloc(0, 8)
    toks = list(range(10, 18))
    cache.insert(toks, table)
    m = cache.match(toks[:6] + [1, 2, 3])  # diverges 2 tokens into block 1
    assert m.n_tokens == 6 and m.blocks == table
    pager.check_invariants(extra_refs=cache.block_refs())


def test_prefix_match_never_covers_whole_prompt():
    """>=1 token is always left to prefill so the engine has logits to
    sample the first output from; capping can drop the tail page."""
    pager, cache = _pager_cache()
    table = pager.alloc(0, 8)
    toks = list(range(20, 28))
    cache.insert(toks, table)
    m = cache.match(toks)  # full coverage must be capped to 7
    assert m.n_tokens == 7 and m.blocks == table
    m = cache.match(toks[:4])  # capped to 3: the only page is dropped? no -
    assert m.n_tokens == 3 and m.blocks == table[:1]
    m = cache.match(toks[:1])
    assert not m.hit  # capping to 0 tokens is a miss


def test_prefix_insert_is_idempotent_and_refcounts_once():
    pager, cache = _pager_cache()
    t0 = pager.alloc(0, 8)
    toks = list(range(30, 38))
    assert cache.insert(toks, t0) == 2
    assert cache.insert(toks, t0) == 0  # re-insert: no double ref
    assert pager.refcount(t0[0]) == 2   # owner + cache, exactly
    # a second request with its own duplicate pages doesn't displace them
    t1 = pager.alloc(1, 8)
    assert cache.insert(toks, t1) == 0
    pager.check_invariants(extra_refs=cache.block_refs())
    pager.free(0)
    pager.free(1)
    pager.check_invariants(extra_refs=cache.block_refs())
    assert len(cache) == 2  # cached pages outlive their owner


def test_prefix_evict_lru_leaves_and_protect():
    pager, cache = _pager_cache()
    t0 = pager.alloc(0, 16)  # 4 blocks, one chain
    cache.insert(list(range(40, 56)), t0)
    pager.free(0)
    t1 = pager.alloc(1, 4)
    cache.insert([1, 2, 3, 4], t1)
    pager.free(1)
    cache.match(list(range(40, 56)))  # refresh the chain's recency
    # only leaves are candidates; the [1,2,3,4] leaf is now the LRU one
    assert cache.evict(1) == [t1[0]]
    # protected pages are skipped
    assert cache.evict(1, protect=frozenset(t0)) == []
    evicted = cache.evict(10)
    assert evicted == list(reversed(t0))  # leaf-first up the chain
    pager.check_invariants()
    assert pager.free_blocks == pager.num_blocks


def _evict_scan_reference(cache, n_blocks, protect=frozenset()):
    """The pre-heap O(nodes x blocks) eviction, kept verbatim as the oracle
    for the lazy-heap rewrite (ISSUE-9 satellite): min last_hit under
    strict <, ties broken by `_by_block` iteration (= node creation) order,
    skipping interior / protected / still-referenced pages."""
    evicted = []
    while len(evicted) < n_blocks:
        best = None
        for node in cache._by_block.values():
            if node.children or node.block in protect:
                continue
            if cache.pager.refcount(node.block) != 1:
                continue
            if best is None or node.last_hit < best.last_hit:
                best = node
        if best is None:
            break
        siblings = best.parent.children if best.parent else cache._children
        del siblings[best.tokens]
        del cache._by_block[best.block]
        cache.pager.release(best.block)
        evicted.append(best.block)
        cache.evictions += 1
    return evicted


def _parity_ops(n=140, seed=321):
    """A deterministic alloc/free/match/evict schedule over a tiny vocab so
    prefixes collide and partially diverge all over the tree."""
    rng = np.random.RandomState(seed)
    header = [int(v) for v in rng.choice(8, size=12)]
    ops, rid = [], 0
    for _ in range(n):
        r = rng.rand()
        toks = [int(v) for v in rng.choice(8, size=int(rng.randint(4, 20)))]
        if rng.rand() < 0.5:
            k = min(len(toks) - 1, 8)
            toks[:k] = header[:k]
        if r < 0.45:
            ops.append(("alloc", rid, toks))
            rid += 1
        elif r < 0.62 and rid:
            ops.append(("free", int(rng.randint(rid))))
        elif r < 0.8:
            ops.append(("match", toks))
        else:
            ops.append(("evict", int(rng.randint(1, 5)),
                        int(rng.randint(3))))
    return ops


def _apply_parity_ops(ops, evict_fn):
    pager = KVPager(num_blocks=32, block_size=4)
    cache = PrefixCache(pager)
    live = set()
    results = []
    for op in ops:
        if op[0] == "alloc":
            _, rid, toks = op
            if pager.can_alloc(len(toks)):
                pager.alloc(rid, len(toks))
                live.add(rid)
                cache.insert(toks, pager.block_table(rid))
        elif op[0] == "free":
            if op[1] in live:
                pager.free(op[1])
                live.remove(op[1])
        elif op[0] == "match":
            cache.match(op[1])
        else:
            _, n, mod = op
            protect = frozenset(b for b in cache._by_block if b % 3 == mod)
            results.append(tuple(evict_fn(cache, n, protect)))
        pager.check_invariants(cache.block_refs())
    for rid in sorted(live):
        pager.free(rid)
    results.append(tuple(evict_fn(cache, 99, frozenset())))
    pager.check_invariants(cache.block_refs())
    return results, sorted(cache._by_block)


def test_evict_heap_matches_reference_scan_order():
    """Satellite 3 parity: the lazy-heap eviction must pick the exact pages
    in the exact order the old full-scan did, through an interleaved
    randomized schedule (including mid-schedule protected evictions and a
    final drain)."""
    ops = _parity_ops()
    heap_res, heap_left = _apply_parity_ops(
        ops, lambda c, n, p: c.evict(n, p))
    ref_res, ref_left = _apply_parity_ops(ops, _evict_scan_reference)
    assert heap_res == ref_res
    assert heap_left == ref_left
    assert any(any(r) for r in heap_res)  # the schedule actually evicted


def test_prefix_evict_skips_pages_still_in_live_tables():
    pager, cache = _pager_cache()
    t0 = pager.alloc(0, 8)
    cache.insert(list(range(60, 68)), t0)
    assert cache.evict(5) == []  # request 0 still reads both pages
    pager.free(0)
    assert len(cache.evict(5)) == 2
    pager.check_invariants()


# ------------------------------------------------- pow2 jit-cache bounding


def test_bucket_len_pow2_with_floor():
    assert [bucket_len(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert bucket_len(3, floor=16) == 16
    assert bucket_len(33, floor=16) == 64
    with pytest.raises(ValueError):
        bucket_len(0)


def test_engine_prefill_jit_cache_is_logarithmic():
    """Satellite 1: serving every prompt length 1..max_len compiles at most
    ~log2(max_len) chunk programs, not one per length."""
    cfg = get_config("yi-6b").reduced().replace(dtype="float32",
                                                param_dtype="float32")
    rng = np.random.default_rng(5)
    max_len = 17
    eng = PagedServingEngine(cfg, block_size=4, num_blocks=32,
                             max_in_flight=2, prefill_chunk=64)
    for n in range(1, max_len + 1):
        eng.submit(rng.integers(0, cfg.vocab, n), max_new_tokens=2)
    eng.run()
    assert len(eng._prefill_fns) <= math.ceil(math.log2(max_len)) + 1


# --------------------------------------------------------- budgeted rounds


def _req(rid, prompt_len, max_new=4):
    return Request(rid=rid, prompt=list(range(1, prompt_len + 1)),
                   max_new_tokens=max_new)


def test_plan_round_respects_token_budget():
    """Decodes are never starved; prefill chunks only spend what's left."""
    pager = KVPager(num_blocks=32, block_size=4)
    sched = ContinuousBatchingScheduler(pager, max_in_flight=8, token_budget=6)
    decoders = [_req(i, 4) for i in range(3)]
    long = _req(9, 40)
    for r in decoders + [long]:
        sched.submit(r)
    for r in sched.admit():
        if r is not long:
            r.prefill_pos = len(r.context)
            sched.promote(r)
    decodes, plans = sched.plan_round(chunk=16)
    assert decodes == decoders
    # 6-token budget minus 3 decodes leaves 3 prefill tokens (chunk caps 16)
    assert plans == [(long, 3)]
    long.prefill_pos += 3
    decodes, plans = sched.plan_round(chunk=2)
    assert plans == [(long, 2)]  # chunk caps below the leftover budget
    # a saturated budget plans zero prefill
    sched.token_budget = 3
    assert sched.plan_round(chunk=16) == (decoders, [])


def test_plan_round_orders_prefill_oldest_first():
    pager = KVPager(num_blocks=32, block_size=4)
    sched = ContinuousBatchingScheduler(pager, max_in_flight=8, token_budget=8)
    a, b = _req(0, 20), _req(1, 20)
    sched.submit(a)
    sched.submit(b)
    sched.admit()
    _, plans = sched.plan_round(chunk=6)
    assert plans == [(a, 6), (b, 2)]  # oldest drains first, b gets the rest


def _f32_cfg():
    return get_config("yi-6b").reduced().replace(dtype="float32",
                                                 param_dtype="float32")


def test_chunked_prefill_does_not_starve_decodes():
    """Satellite 3: a long prompt admitted mid-stream stalls in-flight
    decodes far less when it trickles through chunks than when it lands as
    one monolithic prefill (same engine path, huge chunk). Read from the
    engine's spans, not the wall clock: between two consecutive tokens of
    the short request, chunked prefill runs at most one chunk's tokens,
    while the monolithic engine runs the whole prompt."""
    from repro.obs import trace

    cfg = _f32_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    short = rng.integers(0, cfg.vocab, 4)
    long = rng.integers(0, cfg.vocab, 96)

    def most_prefill_between_tokens(chunk):
        tracer = trace.get_tracer()
        t0 = tracer.now_us()
        token_us = []

        def on_token(req, _tok):
            if req.rid == rid:
                token_us.append(tracer.now_us())

        eng = PagedServingEngine(cfg, block_size=4, num_blocks=64,
                                 params=params, max_in_flight=2,
                                 prefill_chunk=chunk, prefix_cache=False,
                                 on_token=on_token)
        rid = eng.submit(short, max_new_tokens=24)
        eng.step_round()  # the short request starts decoding alone...
        eng.submit(long, max_new_tokens=2)  # ...then the long prompt lands
        eng.run()
        chunks = [(ev["ts"], ev["args"]["n"]) for ev in tracer.events
                  if ev["name"] == "prefill_chunk" and ev["ts"] > t0
                  and ev["args"]["rid"] != rid]
        assert sum(n for _, n in chunks) == len(long)
        return max(sum(n for ts, n in chunks if a < ts < b)
                   for a, b in zip(token_us, token_us[1:]))

    assert most_prefill_between_tokens(8) == 8   # one chunk, never more
    assert most_prefill_between_tokens(512) == len(long)


def test_chunked_prefill_feeds_pipeline_telemetry():
    """Warm prefill chunks land in the `paged_prefill` transfer-feedback
    store (the first observation per tile count is compile warmup)."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(6)
    autotune.set_telemetry(True)
    eng = PagedServingEngine(cfg, block_size=4, num_blocks=32,
                             max_in_flight=1, prefill_chunk=8,
                             prefix_cache=False)
    for _ in range(4):  # identical shapes: same buckets, same tile counts
        eng.submit(rng.integers(0, cfg.vocab, 16), max_new_tokens=2)
    eng.run()
    assert len(autotune.transfer_samples("paged_prefill")) > 0
    assert "paged_prefill" in autotune.telemetry_summary()["kernels"]


# ------------------------------------------------------------- end-to-end


def test_engine_shared_prefix_dedups_pages_token_identical():
    """The acceptance scenario: 8 requests sharing a 3-block prefix, pool
    admissions staggered so the cache is warm after the first. >=7 hit,
    strictly fewer physical pages are allocated than without the cache, and
    every emitted token is identical (greedy parity, float32)."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(7)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    blk = 4
    shared = list(rng.integers(0, cfg.vocab, 3 * blk))
    prompts = [shared + list(rng.integers(0, cfg.vocab, 3 + i % 4))
               for i in range(8)]

    def run(prefix_cache):
        eng = PagedServingEngine(cfg, block_size=blk, num_blocks=48,
                                 params=params, max_in_flight=1,
                                 prefix_cache=prefix_cache)
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        stats = eng.run()  # run() checks refcount invariants at drain
        return [eng.request(r).generated for r in rids], stats

    warm_toks, warm = run(True)
    cold_toks, cold = run(False)
    assert warm_toks == cold_toks
    assert warm["prefix_hits"] >= 7
    assert warm["blocks_allocated"] < cold["blocks_allocated"]
    assert warm["blocks_shared"] >= 7 * 3
    assert warm["prefix_tokens"] >= 7 * len(shared)
    assert cold["prefix_hits"] == 0 and cold["blocks_shared"] == 0


def test_engine_cow_divergence_mid_block():
    """Two prompts diverging inside a block: the second shares the partial
    page, CoW-forks it before writing its own suffix rows, and both emit
    exactly what they emit without any sharing."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shared = list(rng.integers(0, cfg.vocab, 6))  # 1.5 blocks at blk=4
    pa = shared + [11, 22, 33]
    pb = shared + [44, 55, 66]

    def run(prefix_cache):
        eng = PagedServingEngine(cfg, block_size=4, num_blocks=32,
                                 params=params, max_in_flight=1,
                                 prefix_cache=prefix_cache)
        rids = [eng.submit(p, max_new_tokens=4) for p in (pa, pb)]
        stats = eng.run()
        return [eng.request(r).generated for r in rids], stats

    warm_toks, warm = run(True)
    cold_toks, cold = run(False)
    assert warm_toks == cold_toks
    assert warm["cow_forks"] >= 1  # the divergence actually forked a page
    assert warm["prefix_hits"] == 1 and warm["prefix_tokens"] == 6


def test_engine_preempted_request_rehits_its_own_pages():
    """Preemption + prefix cache: the victim's recompute-on-readmit turns
    into a prefix hit on its own surviving cached pages."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(9)
    blk, gen = 4, 6
    plens = [10, 10, 10]
    blocks_per_req = -(-(max(plens) + gen) // blk)
    eng = PagedServingEngine(cfg, block_size=blk,
                             num_blocks=blocks_per_req + 2, max_in_flight=3)
    rids = [eng.submit(rng.integers(0, cfg.vocab, n), max_new_tokens=gen)
            for n in plens]
    stats = eng.run()
    assert stats["completed"] == len(plens)
    for rid in rids:
        assert len(eng.request(rid).generated) == gen


def test_admission_waits_for_a_prefix_still_in_prefill():
    """Two requests sharing two whole blocks, with room to run both at
    once: the second waits until the first has written the shared pages,
    then admits as a cache hit instead of prefilling them a second time.
    Without the cache both admit at once. Tokens are identical."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(9)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    shared = list(rng.integers(0, cfg.vocab, 8))        # 2 blocks at blk=4
    prompts = [shared + [1, 2, 3], shared + [4, 5]]

    def run(prefix_cache):
        eng = PagedServingEngine(cfg, block_size=4, num_blocks=32,
                                 params=params, max_in_flight=4,
                                 prefill_chunk=4, prefix_cache=prefix_cache)
        rids = [eng.submit(p, max_new_tokens=3) for p in prompts]
        eng.step_round()
        admitted = eng.scheduler.in_flight()
        stats = eng.run()
        return [eng.request(r).generated for r in rids], admitted, stats

    warm_toks, warm_admitted, warm = run(True)
    cold_toks, cold_admitted, cold = run(False)
    assert warm_toks == cold_toks
    assert (warm_admitted, cold_admitted) == (1, 2)
    assert warm["prefix_hits"] == 1 and warm["prefix_tokens"] == 8
    assert warm["blocks_allocated"] < cold["blocks_allocated"]
