"""Serving failure model (ISSUE-9): the partial-prefix pool-pressure crash
regression, deterministic fault injection, deadlines / cancel / shed,
quarantine + stall accounting, and pool-pressure fuzz on 1-4 block pools —
every submitted request must reach a terminal state with the pager
invariants intact, no matter what the pool or the injected chaos does."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import (
    TERMINAL_STATES,
    ContinuousBatchingScheduler,
    FaultInjector,
    InjectedFault,
    KVPager,
    NULL_INJECTOR,
    PagedServingEngine,
    PoolExhausted,
    PrefixCache,
    Request,
    RequestState,
)

# --------------------------------------------------------- fault injector


def test_injector_is_deterministic_across_instances():
    a, b = FaultInjector(7), FaultInjector(7)
    seq_a = [a.fire("decode") for _ in range(300)]
    seq_b = [b.fire("decode") for _ in range(300)]
    assert seq_a == seq_b
    assert any(seq_a) and not all(seq_a)  # the default rate actually draws
    assert a.stats() == b.stats()


def test_injector_sites_are_independent_streams():
    """Draining one site must not perturb another's n-th decision."""
    rates = {"decode": 0.5, "prefill": 0.5}
    a = FaultInjector(3, rates=rates)
    b = FaultInjector(3, rates=rates)
    for _ in range(200):
        a.fire("decode")  # only a drains the decode stream
    seq_a = [a.fire("prefill") for _ in range(200)]
    seq_b = [b.fire("prefill") for _ in range(200)]
    assert seq_a == seq_b


def test_injector_rate_bounds_and_unknown_sites():
    inj = FaultInjector(0, rates={"decode": 1.0, "prefill": 0.0})
    assert all(inj.fire("decode") for _ in range(50))
    assert not any(inj.fire("prefill") for _ in range(50))
    assert inj.fire("latency") is False  # unlisted in rates: never fires
    assert inj.by_site == {"decode": 50}
    with pytest.raises(ValueError):
        FaultInjector(0, rates={"not_a_site": 0.5})


def test_injector_check_raises_and_max_faults_caps():
    inj = FaultInjector(0, rates={"decode": 1.0}, max_faults=2)
    with pytest.raises(InjectedFault):
        inj.check("decode")
    with pytest.raises(InjectedFault):
        inj.check("decode")
    inj.check("decode")  # budget spent: the site goes quiet
    assert inj.injected == 2 and inj.by_site == {"decode": 2}
    assert len(inj.log) == 2


def test_injector_latency_spike_magnitude_bounds():
    inj = FaultInjector(1, rates={"latency": 1.0}, latency_spike_s=1e-3)
    for _ in range(25):
        s = inj.latency_spike()
        assert 0.5e-3 <= s <= 1.5e-3
    quiet = FaultInjector(1, rates={"latency": 0.0})
    assert quiet.latency_spike() == 0.0


def test_null_injector_is_inert():
    assert NULL_INJECTOR.fire("decode") is False
    NULL_INJECTOR.check("decode")  # never raises
    assert NULL_INJECTOR.latency_spike() == 0.0
    assert NULL_INJECTOR.injected == 0
    assert NULL_INJECTOR.enabled is False
    assert NULL_INJECTOR.stats()["by_site"] == {}


# ------------------------------------------------------------------ pager


def test_pager_pop_token_rolls_back_reservation():
    pager = KVPager(num_blocks=4, block_size=4)
    pager.alloc(0, 4)  # exactly one full block
    pos = pager.append_token(0)  # reservation grows a second block
    assert pos == 4 and len(pager.block_table(0)) == 2
    pager.pop_token(0)  # the round raised: undo
    assert pager.length(0) == 4 and len(pager.block_table(0)) == 1
    pager.check_invariants()
    # mid-block pop leaves the table alone
    pager.append_token(0)
    pager.append_token(0)
    pager.pop_token(0)
    assert pager.length(0) == 5 and len(pager.block_table(0)) == 2
    pager.check_invariants()


def test_pager_pop_token_without_reservation_raises():
    pager = KVPager(num_blocks=2, block_size=4)
    pager.alloc(0, 1)
    pager.pop_token(0)  # down to zero tokens frees the page
    assert pager.length(0) == 0 and pager.free_blocks == 2
    with pytest.raises(ValueError):
        pager.pop_token(0)
    pager.check_invariants()


def test_pager_injected_exhaustion_rolls_back_partial_claim():
    """An injected PoolExhausted mid-alloc must leave no leak behind —
    neither half-popped fresh blocks nor prefix refcounts."""
    pager = KVPager(num_blocks=8, block_size=4)
    t0 = pager.alloc(0, 8)
    cached = t0[0]
    pager.share(cached)  # emulate the prefix cache keeping the page alive
    pager.free(0)
    assert pager.refcount(cached) == 1 and pager.free_blocks == 7
    pager.faults = FaultInjector(0, rates={"pool_exhausted": 1.0})
    with pytest.raises(PoolExhausted):
        pager.alloc(1, 12, prefix_blocks=[cached], prefix_len=4)
    pager.check_invariants({cached: 1})
    assert pager.refcount(cached) == 1  # the failed claim's ref rolled back
    assert pager.free_blocks == 7 and not pager.owns(1)


# ------------------------------------- the reproduced crash (satellite 1)


def _cache_partial_prefix(pager, cache, prompt):
    """Simulate request A: prefill `prompt`, cache its full blocks, finish."""
    pager.alloc(0, len(prompt))
    cache.insert(prompt, pager.block_table(0))
    pager.free(0)


def test_admit_reserves_cow_block_for_partial_prefix_match():
    """ISSUE-9 reproduced crash, scheduler-level: 2-block pool, one cached
    page, a prompt matching it mid-block. On main, `admit` claimed the last
    free block for the suffix and the first suffix write then had to fork
    the shared partial page with zero free blocks, zero evictable pages
    (the match is refcounted >= 2) and zero preemption victims —
    PoolExhausted escaped. Admission must reserve the fork's block (or give
    the match up), so the first write never raises."""
    pager = KVPager(num_blocks=2, block_size=4)
    cache = PrefixCache(pager)
    a = list(range(100, 105))  # 5 tokens = 2 blocks; the first gets cached
    _cache_partial_prefix(pager, cache, a)
    assert pager.free_blocks == 1 and len(cache) == 1

    sched = ContinuousBatchingScheduler(
        pager, 2, reclaim=lambda n, p: len(cache.evict(n, p)))
    b = Request(rid=1, prompt=a[:2] + [7, 8, 9, 10, 11], max_new_tokens=1)
    sched.submit(b)
    assert sched.admit(match=cache.match) == [b]
    sched.make_writable(b, b.prefill_pos)  # the first-write fork
    pager.check_invariants(cache.block_refs())


def test_admit_keeps_partial_match_when_pool_has_the_spare_block():
    """Same shape, 3-block pool: sharing must survive — the reserve comes
    from the pool, not from giving the match up."""
    pager = KVPager(num_blocks=3, block_size=4)
    cache = PrefixCache(pager)
    a = list(range(100, 105))
    _cache_partial_prefix(pager, cache, a)
    assert pager.free_blocks == 2

    sched = ContinuousBatchingScheduler(
        pager, 2, reclaim=lambda n, p: len(cache.evict(n, p)))
    b = Request(rid=1, prompt=a[:2] + [7, 8, 9, 10, 11], max_new_tokens=1)
    sched.submit(b)
    assert sched.admit(match=cache.match) == [b]
    assert b.matched_len == 2  # the partial-block hit was kept
    copy = sched.make_writable(b, b.prefill_pos)
    assert copy is not None  # the fork spent the reserved block
    assert len(cache) == 1  # nothing was sacrificed
    pager.check_invariants(cache.block_refs())


# ------------------------------------------------------------- tiny engine


def _f32_cfg():
    return get_config("yi-6b").reduced().replace(dtype="float32",
                                                 param_dtype="float32")


@pytest.fixture(scope="module")
def tiny():
    cfg = _f32_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _eng(tiny, **kw):
    cfg, params = tiny
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 16)
    return PagedServingEngine(cfg, params=params, **kw)


def _drive_checked(eng, max_rounds=500):
    """step_round until drained, asserting pager invariants EVERY round."""
    rounds = 0
    while eng.scheduler.has_work() and rounds < max_rounds:
        eng.step_round()
        rounds += 1
        eng.pager.check_invariants(
            eng.prefix_cache.block_refs() if eng.prefix_cache else None)
    return eng.run()  # drains stragglers + final invariant check


def test_engine_partial_prefix_tight_pool_survives(tiny):
    """The crash end-to-end: request A caches a page and finishes; request
    B's prompt matches it mid-block in a 2-block pool. On main,
    PoolExhausted escaped `run()` on B's first prefill write. Now B
    completes — via admission's CoW reserve, with no stall fallback."""
    rng = np.random.default_rng(42)
    eng = _eng(tiny, num_blocks=2)
    a_prompt = rng.integers(0, eng.cfg.vocab, 5)
    rid_a = eng.submit(a_prompt, max_new_tokens=1)
    eng.run()
    assert eng.request(rid_a).state is RequestState.FINISHED
    assert eng.pager.free_blocks == 1 and len(eng.prefix_cache) == 1

    b_prompt = list(a_prompt[:2]) + [int(t) for t in
                                     rng.integers(0, eng.cfg.vocab, 5)]
    rid_b = eng.submit(b_prompt, max_new_tokens=1)
    stats = eng.run()  # on main: raise PoolExhausted
    req = eng.request(rid_b)
    assert req.state is RequestState.FINISHED
    assert len(req.generated) == 1
    assert stats["stalls"] == 0  # admission solved it, not stall-retry


def test_engine_pool_pressure_fuzz_tiny_pools(tiny):
    """Satellite 4: randomized workloads on 1-4 block pools; invariants
    hold after every round, nothing escapes, everything goes terminal."""
    for num_blocks in (1, 2, 3, 4):
        rng = np.random.default_rng(100 + num_blocks)
        eng = _eng(tiny, num_blocks=num_blocks)
        cap = num_blocks * eng.pager.block_size
        rids = []
        for _ in range(5):
            total = int(rng.integers(2, cap + 1))
            gen = int(rng.integers(1, min(total, 3)))
            prompt = rng.integers(0, eng.cfg.vocab, total - gen)
            rids.append(eng.submit(prompt, max_new_tokens=gen))
        stats = _drive_checked(eng)
        assert all(eng.request(r).terminal for r in rids)
        assert stats["completed"] == len(rids)  # no faults: all complete
        assert stats["failed"] == 0 and stats["live"] == 0


@pytest.mark.slow
def test_engine_pool_pressure_fuzz_long_sweep(tiny):
    """The long arm of the fuzz: more seeds, staggered arrivals, chaos on."""
    for seed in range(4):
        rng = np.random.default_rng(1000 + seed)
        num_blocks = int(rng.integers(2, 7))
        inj = FaultInjector(seed, rates={"pool_exhausted": 0.05,
                                         "reclaim_refuse": 0.1,
                                         "preempt_refuse": 0.05,
                                         "decode": 0.03, "prefill": 0.03})
        eng = _eng(tiny, num_blocks=num_blocks, faults=inj)
        cap = num_blocks * eng.pager.block_size
        rids = []
        for burst in range(3):
            for _ in range(4):
                total = int(rng.integers(2, cap + 1))
                gen = int(rng.integers(1, min(total, 4)))
                prompt = rng.integers(0, eng.cfg.vocab, total - gen)
                rids.append(eng.submit(prompt, max_new_tokens=gen))
            for _ in range(int(rng.integers(1, 5))):
                eng.step_round()
                eng.pager.check_invariants(eng.prefix_cache.block_refs())
        stats = _drive_checked(eng)
        assert all(eng.request(r).terminal for r in rids)
        assert (stats["completed"] + stats["cancelled"]
                + stats["failed"]) == len(rids)


# ----------------------------------------------------------------- chaos


def test_engine_chaos_every_request_terminal_and_replayable(tiny):
    """A seeded fault schedule (decode/prefill exceptions, pool exhaustion,
    refusals) degrades gracefully — every request terminal, invariants hold
    — and replays bit-for-bit: same outcomes, same tokens, same injector
    counts across two identical runs."""
    rates = {"pool_exhausted": 0.1, "reclaim_refuse": 0.2,
             "preempt_refuse": 0.1, "decode": 0.1, "prefill": 0.1}

    def run():
        rng = np.random.default_rng(17)
        inj = FaultInjector(5, rates=rates)
        eng = _eng(tiny, num_blocks=6, faults=inj, max_in_flight=3)
        shared = rng.integers(0, eng.cfg.vocab, 6)
        rids = []
        for i in range(6):
            prompt = rng.integers(0, eng.cfg.vocab, int(rng.integers(3, 9)))
            if i % 2 == 0:
                n = min(len(shared), len(prompt) - 1)
                prompt[:n] = shared[:n]
            rids.append(eng.submit(prompt, max_new_tokens=2))
        stats = _drive_checked(eng)
        outcomes = [(eng.request(r).state, eng.request(r).finish_reason,
                     tuple(eng.request(r).generated)) for r in rids]
        return outcomes, inj.stats(), stats

    outcomes1, inj1, stats1 = run()
    outcomes2, inj2, _ = run()
    assert all(state in TERMINAL_STATES for state, _, _ in outcomes1)
    assert outcomes1 == outcomes2
    assert inj1 == inj2
    assert inj1["injected"] == stats1["faults_injected"] > 0


def test_engine_decode_poison_quarantines_only_the_requests(tiny):
    """A decode round that always raises must not crash the engine: the
    members share the blame and are quarantined after max_request_faults,
    with their pages freed and the error recorded."""
    inj = FaultInjector(0, rates={"decode": 1.0})
    eng = _eng(tiny, num_blocks=8, faults=inj, max_request_faults=2)
    rng = np.random.default_rng(3)
    rid = eng.submit(rng.integers(0, eng.cfg.vocab, 5), max_new_tokens=3)
    stats = eng.run()
    req = eng.request(rid)
    assert req.state is RequestState.FAILED
    assert req.finish_reason == "fault"
    assert "InjectedFault" in req.error
    assert stats["failed"] == 1 and stats["step_faults"] == 3
    assert stats["completed"] == 0
    # quarantine freed the request's pages; only cached pages remain
    assert eng.pager.free_blocks + len(eng.prefix_cache) == 8


def test_engine_prefill_poison_quarantines(tiny):
    inj = FaultInjector(0, rates={"prefill": 1.0})
    eng = _eng(tiny, faults=inj, max_request_faults=2)
    rid = eng.submit([5, 6, 7, 8, 9], max_new_tokens=2)
    stats = eng.run()
    req = eng.request(rid)
    assert req.state is RequestState.FAILED and req.finish_reason == "fault"
    assert stats["failed"] == 1


def test_engine_recovers_from_transient_fault(tiny):
    """One injected decode failure, then clear air: the request retries the
    round and completes — transient faults cost a round, not the request."""
    inj = FaultInjector(0, rates={"decode": 1.0}, max_faults=1)
    eng = _eng(tiny, faults=inj)
    rid = eng.submit([3, 1, 4, 1, 5], max_new_tokens=4)
    stats = eng.run()
    req = eng.request(rid)
    assert req.state is RequestState.FINISHED
    assert len(req.generated) == 4
    assert stats["step_faults"] == 1 and stats["failed"] == 0
    assert req.fault_count == 0  # success cleared the shared blame


# ----------------------------------------- deadlines / cancel / shed / run


def test_engine_deadline_expires_and_overrides(tiny):
    """Engine-default deadline 0 cancels at the first round boundary; a
    generous per-request override completes normally."""
    done = []
    eng = _eng(tiny, deadline_s=0.0,
               on_finish=lambda r: done.append(r.rid))
    doomed = [eng.submit([1, 2, 3], max_new_tokens=2) for _ in range(2)]
    saved = eng.submit([4, 5, 6], max_new_tokens=2, deadline_s=60.0)
    stats = eng.run()
    for rid in doomed:
        req = eng.request(rid)
        assert req.state is RequestState.CANCELLED
        assert req.finish_reason == "deadline"
    assert eng.request(saved).state is RequestState.FINISHED
    assert stats["deadline_expired"] == 2 and stats["cancelled"] == 2
    assert stats["completed"] == 1
    assert sorted(done) == sorted(doomed + [saved])  # on_finish fired for all


def test_engine_cancel_mid_flight(tiny):
    done = []
    eng = _eng(tiny, on_finish=lambda r: done.append(r.rid))
    rng = np.random.default_rng(4)
    r0 = eng.submit(rng.integers(0, eng.cfg.vocab, 6), max_new_tokens=6)
    r1 = eng.submit(rng.integers(0, eng.cfg.vocab, 6), max_new_tokens=6)
    eng.step_round()
    eng.step_round()  # both in flight now
    assert eng.cancel(r0) is True
    assert eng.cancel(r0) is False  # already terminal: idempotent
    assert eng.cancel(999) is False  # unknown rid
    eng.pager.check_invariants(eng.prefix_cache.block_refs())  # pages freed
    stats = eng.run()
    assert eng.request(r0).state is RequestState.CANCELLED
    assert eng.request(r0).finish_reason == "cancelled"
    assert eng.request(r1).state is RequestState.FINISHED
    assert len(eng.request(r1).generated) == 6
    assert stats["cancelled"] == 1 and r0 in done and r1 in done


def test_engine_sheds_on_admission_overflow(tiny):
    done = []
    eng = _eng(tiny, max_queue=2, on_finish=lambda r: done.append(r.rid))
    rng = np.random.default_rng(6)
    rids = [eng.submit(rng.integers(0, eng.cfg.vocab, 4), max_new_tokens=1)
            for _ in range(5)]
    shed = [r for r in rids if eng.request(r).state is RequestState.FAILED]
    assert len(shed) == 3  # queue held 2; the rest were shed at submit
    for rid in shed:
        assert eng.request(rid).finish_reason == "shed"
        assert rid in done  # the callback contract holds for shed too
    stats = eng.run()
    assert stats["completed"] == 2 and stats["shed"] == 3
    assert stats["failed"] == 3


def test_engine_run_returns_partial_stats_when_wedged(tiny):
    """A workload that can never be admitted must not spin `run()` forever
    or raise: past the idle bound the remainder is CANCELLED ("stalled")
    and the stats come back with the accounting."""
    eng = _eng(tiny, num_blocks=4)
    eng.pager.alloc(999, eng.pager.pool_tokens)  # squatter pins every block
    rid = eng.submit([1, 2, 3, 4], max_new_tokens=2)
    stats = eng.run(max_idle_rounds=3)
    req = eng.request(rid)
    assert req.state is RequestState.CANCELLED
    assert req.finish_reason == "stalled"
    assert stats["stalled"] == 1 and stats["live"] == 0


def test_engine_run_respects_max_rounds(tiny):
    eng = _eng(tiny)
    rid = eng.submit([9, 8, 7], max_new_tokens=4)
    stats = eng.run(max_rounds=0)
    assert eng.request(rid).finish_reason == "stalled"
    assert stats["stalled"] == 1


# ------------------------------------------------- table width (satellite 2)


def test_engine_table_width_tracks_live_requests_only(tiny):
    """The decode-table width follows the LIVE worst case with a high-water
    guard: one long retired request no longer pins the width forever, and
    lookups still resolve through the retired map."""
    rng = np.random.default_rng(8)
    eng = _eng(tiny, num_blocks=16)
    assert eng._table_width() == 1
    short = eng.submit(rng.integers(0, eng.cfg.vocab, 3), max_new_tokens=2)
    long = eng.submit(rng.integers(0, eng.cfg.vocab, 30), max_new_tokens=2)
    assert eng._table_width() == 8  # blocks_for(32): the long request
    stats = eng.run()
    assert stats["completed"] == 2
    assert eng._requests == {}  # terminal requests leave the live map
    assert eng.request(long).state is RequestState.FINISHED  # still findable
    assert eng.request(short).state is RequestState.FINISHED
    assert eng._table_width() == 1  # the mark fell with the live need
    # the shrink is hysteretic: a mid-size live request re-grows cleanly
    mid = eng.submit(rng.integers(0, eng.cfg.vocab, 14), max_new_tokens=2)
    assert eng._table_width() == 4
    eng.run()
    assert eng.request(mid).state is RequestState.FINISHED


# ----------------------------------------------------------- chaos harness


@pytest.mark.slow
def test_chaos_serve_script_smoke():
    """scripts/chaos_serve.py (the CI chaos-smoke lane) runs green and its
    summary accounts for every request."""
    root = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "scripts/chaos_serve.py", "--seed", "1",
         "--rounds", "30", "--requests", "4"],
        cwd=root, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["requests"] == 4
    assert (summary["completed"] + summary["cancelled"]
            + summary["failed"]) == 4


# ------------------------------------------------------------ strict mode


def _break_site(eng, site, monkeypatch):
    """Make one step site raise what a chip raises on a compile failure or
    an HBM OOM — an error the fault injector did not cause."""
    def boom(*args, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory in HBM")
    if site == "decode":
        monkeypatch.setattr(eng, "_decode", lambda table_width: boom)
    else:
        monkeypatch.setattr(eng.prefiller, "run_chunk", boom)


@pytest.mark.parametrize("site", ["decode", "prefill"])
def test_strict_engine_reraises_a_real_step_error(tiny, monkeypatch, site):
    from repro.core import guard
    eng = _eng(tiny)
    _break_site(eng, site, monkeypatch)
    eng.submit([3, 1, 4, 1, 5], max_new_tokens=3)
    guard.set_strict(True)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.run()
    assert eng.stats()["step_faults"] == 0


@pytest.mark.parametrize("site", ["decode", "prefill"])
def test_lenient_engine_quarantines_a_real_step_error(tiny, monkeypatch,
                                                      site):
    eng = _eng(tiny, max_request_faults=1)
    _break_site(eng, site, monkeypatch)
    rid = eng.submit([3, 1, 4, 1, 5], max_new_tokens=3)
    stats = eng.run()
    assert eng.request(rid).state is RequestState.FAILED
    assert "RESOURCE_EXHAUSTED" in eng.request(rid).error
    assert stats["step_faults"] == 2


@pytest.mark.parametrize("site", ["decode", "prefill", "kernel_compile",
                                  "kernel_oom"])
def test_strict_engine_still_absorbs_injected_faults(tiny, site):
    """Chaos keeps its recovery path under strict mode: an injected fault
    costs a retry, not the run."""
    from repro.core import guard
    guard.set_strict(True)
    inj = FaultInjector(0, rates={site: 1.0}, max_faults=1)
    eng = _eng(tiny, faults=inj)
    rid = eng.submit([3, 1, 4, 1, 5], max_new_tokens=4)
    stats = eng.run()
    assert eng.request(rid).state is RequestState.FINISHED
    assert len(eng.request(rid).generated) == 4
    assert stats["step_faults"] == 1 and inj.injected == 1


def test_decode_round_checks_logits_on_device(tiny, monkeypatch):
    """The decode step returns a finite flag per row; a non-finite row is a
    numerics fault of the round (counted, and raised under strict mode)."""
    import jax.numpy as jnp
    eng = _eng(tiny, max_request_faults=0)
    real = eng._decode

    def poisoned(table_width):
        step = real(table_width)

        def run(*args):
            nxt, finite, k, v = step(*args)
            return nxt, jnp.zeros_like(finite), k, v
        return run

    monkeypatch.setattr(eng, "_decode", poisoned)
    rid = eng.submit([3, 1, 4, 1, 5], max_new_tokens=3)
    stats = eng.run()
    assert eng.request(rid).state is RequestState.FAILED
    assert "KernelNumericsError" in eng.request(rid).error
    assert stats["substrate"]["numerics_faults"] == 1
