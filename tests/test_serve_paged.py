"""Paged-KV serving subsystem: pager invariants, scheduler pressure,
paged decode parity on ragged lengths, engine end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.decode_attention.ref import paged_decode_attention_ref
from repro.models import build_model
from repro.models import common as mc
from repro.models import lm
from repro.serve import (
    ContinuousBatchingScheduler,
    KVPager,
    PagedServingEngine,
    PoolExhausted,
    Request,
    RequestState,
)
from repro.sharding import NULL_CTX


# ------------------------------------------------------------------- pager


def test_pager_randomized_schedule_no_leak_no_double_own(rng):
    """Blocks stay free-xor-owned through a randomized admit/evict/append
    schedule; the garbage page is never handed out."""
    pager = KVPager(num_blocks=24, block_size=4)
    live = []
    next_rid = 0
    for _ in range(600):
        op = rng.choice(["alloc", "append", "free"])
        if op == "alloc":
            n = int(rng.randint(1, 30))
            if pager.can_alloc(n):
                pager.alloc(next_rid, n)
                live.append(next_rid)
                next_rid += 1
            else:
                with pytest.raises(PoolExhausted):
                    pager.alloc(next_rid, n)
        elif op == "append" and live:
            rid = live[rng.randint(len(live))]
            try:
                pos = pager.append_token(rid)
                assert pos == pager.length(rid) - 1
            except PoolExhausted:
                assert pager.free_blocks == 0
        elif op == "free" and live:
            rid = live.pop(rng.randint(len(live)))
            pager.free(rid)
        pager.check_invariants()
    for rid in live:
        pager.free(rid)
    pager.check_invariants()
    assert pager.free_blocks == pager.num_blocks


def test_pager_failed_alloc_leaves_state_intact():
    pager = KVPager(num_blocks=4, block_size=4)
    pager.alloc(0, 12)  # 3 blocks
    with pytest.raises(PoolExhausted):
        pager.alloc(1, 8)  # needs 2, only 1 free
    pager.check_invariants()
    assert pager.free_blocks == 1
    assert not pager.owns(1)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_pager_refcount_fuzz_share_fork_free_evict(seed):
    """Refcount state machine under randomized share/fork/free/evict
    interleavings: every usable block stays free xor owned-by-one xor
    shared-by-many (plus emulated external cache refs), the garbage page is
    never refcounted, and draining everything returns the whole pool."""
    rng = np.random.RandomState(1000 + seed)
    pager = KVPager(num_blocks=16, block_size=4)
    live, next_rid = [], 0
    cache_refs = {}  # emulated prefix-cache references

    for _ in range(500):
        op = rng.choice(["alloc", "append", "share", "evict", "free", "fork"])
        if op == "alloc":
            n = int(rng.randint(1, 20))
            if pager.can_alloc(n):
                pager.alloc(next_rid, n)
                live.append(next_rid)
                next_rid += 1
        elif op == "append" and live:
            rid = live[rng.randint(len(live))]
            try:
                pos = pager.append_token(rid)
                pager.ensure_writable(rid, pos)  # CoW if the page is shared
            except PoolExhausted:
                assert pager.free_blocks == 0
        elif op == "share" and live:
            rid = live[rng.randint(len(live))]
            table = pager.block_table(rid)
            b = table[rng.randint(len(table))]
            pager.share(b)
            cache_refs[b] = cache_refs.get(b, 0) + 1
        elif op == "evict" and cache_refs:
            b = list(cache_refs)[rng.randint(len(cache_refs))]
            pager.release(b)
            cache_refs[b] -= 1
            if cache_refs[b] == 0:
                del cache_refs[b]
        elif op == "free" and live:
            rid = live.pop(rng.randint(len(live)))
            pager.free(rid)  # cache-shared pages must survive this
        elif op == "fork" and live:
            rid = live[rng.randint(len(live))]
            pos = rng.randint(pager.length(rid))
            try:
                copy = pager.ensure_writable(rid, pos)
            except PoolExhausted:
                assert pager.free_blocks == 0
                continue
            if copy is not None:
                src, dst = copy
                assert src != dst
                assert dst in pager.block_table(rid)
                assert src not in pager.block_table(rid)
        pager.check_invariants(extra_refs=cache_refs)

    for rid in live:
        pager.free(rid)
    for b, n in list(cache_refs.items()):
        for _ in range(n):
            pager.release(b)
    pager.check_invariants()
    assert pager.free_blocks == pager.num_blocks


def test_pager_cow_forks_only_shared_pages():
    """ensure_writable is a no-op on private pages, forks shared ones, and
    the fork leaves the original alive for its other reference."""
    pager = KVPager(num_blocks=8, block_size=4)
    t0 = pager.alloc(0, 8)  # two blocks
    assert pager.ensure_writable(0, 5) is None  # private: nothing to do
    pager.share(t0[1])  # emulate a prefix-cache ref on block 1
    src, dst = pager.ensure_writable(0, 5)
    assert src == t0[1] and dst != src
    assert pager.refcount(src) == 1 and pager.refcount(dst) == 1
    pager.check_invariants(extra_refs={src: 1})
    pager.free(0)
    pager.release(src)
    pager.check_invariants()
    assert pager.free_blocks == pager.num_blocks


def test_pager_prefix_alloc_shares_blocks():
    """alloc(prefix_blocks=...) increfs resident pages instead of popping
    fresh ones; freeing either owner keeps the other's view alive."""
    pager = KVPager(num_blocks=8, block_size=4)
    ta = pager.alloc(0, 12)  # 3 blocks
    popped = pager.blocks_allocated
    tb = pager.alloc(1, 12, prefix_blocks=ta[:2], prefix_len=8)
    assert pager.blocks_allocated == popped + 1  # only the suffix popped
    assert tb[:2] == ta[:2] and tb[2] != ta[2]
    assert pager.refcount(ta[0]) == 2
    pager.check_invariants()
    pager.free(0)
    assert pager.refcount(ta[0]) == 1  # request 1 still reads it
    pager.check_invariants()
    pager.free(1)
    pager.check_invariants()
    assert pager.free_blocks == pager.num_blocks
    with pytest.raises(ValueError):
        # a full-prompt prefix must still leave >= 1 token to prefill
        pager.alloc(2, 8, prefix_blocks=[1, 2], prefix_len=8)


def test_pager_padded_table_uses_garbage_page():
    pager = KVPager(num_blocks=8, block_size=4)
    pager.alloc(7, 10)
    t = pager.padded_table(7, 6)
    assert t.shape == (6,) and t.dtype == np.int32
    assert (t[3:] == 0).all() and (t[:3] > 0).all()


# --------------------------------------------------------------- scheduler


def _req(rid, prompt_len, max_new=4):
    return Request(rid=rid, prompt=list(range(1, prompt_len + 1)),
                   max_new_tokens=max_new)


def test_scheduler_admission_bounded_by_pool_and_round_width():
    pager = KVPager(num_blocks=4, block_size=4)
    sched = ContinuousBatchingScheduler(pager, max_in_flight=8)
    for rid in range(3):
        sched.submit(_req(rid, prompt_len=8))  # 2 blocks each
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [0, 1]  # third doesn't fit
    assert sched.admit() == []
    sched.finish(admitted[0])
    assert [r.rid for r in sched.admit()] == [2]
    pager.check_invariants()


def test_scheduler_admission_is_fifo_under_pressure():
    """A big head request blocks smaller ones behind it (no starvation)."""
    pager = KVPager(num_blocks=4, block_size=4)
    sched = ContinuousBatchingScheduler(pager, max_in_flight=8)
    sched.submit(_req(0, prompt_len=8))
    sched.submit(_req(1, prompt_len=30))  # 8 blocks: never fits beside rid 0
    sched.submit(_req(2, prompt_len=4))
    assert [r.rid for r in sched.admit()] == [0]
    assert sched.admit() == []  # rid 2 must wait its turn behind rid 1


def test_scheduler_preempts_latest_admitted_on_growth():
    pager = KVPager(num_blocks=3, block_size=4)
    sched = ContinuousBatchingScheduler(pager, max_in_flight=4)
    a, b, c = _req(0, 4), _req(1, 4), _req(2, 4)
    for r in (a, b, c):
        sched.submit(r)
    admitted = sched.admit()
    assert len(admitted) == 3  # one block each, pool now full
    for r in admitted:
        sched.promote(r)  # prefill done; decode from here on
    # growing the oldest evicts the newest, never the oldest itself
    for _ in range(pager.block_size):
        sched.reserve_decode_slot(a)
    assert c.state is RequestState.WAITING and c.preemptions == 1
    assert a.state is RequestState.RUNNING
    assert sched.waiting[0] is c  # re-queued at the front
    pager.check_invariants()


def test_scheduler_lone_request_overflow_raises():
    pager = KVPager(num_blocks=1, block_size=2)
    sched = ContinuousBatchingScheduler(pager, max_in_flight=2)
    r = _req(0, prompt_len=2)
    sched.submit(r)
    sched.admit()
    with pytest.raises(PoolExhausted):
        sched.reserve_decode_slot(r)  # nothing else to evict


# ------------------------------------------------- paged attention parity


def _paged_problem(rng, lengths, *, h, kh, d, blk, extra_blocks=3):
    """Random pools + disjoint shuffled block tables for given ragged
    lengths. Returns (q, k_pool, v_pool, block_tables [B, M])."""
    lengths = np.asarray(lengths, np.int32)
    bsz = len(lengths)
    nb_per = [-(-int(n) // blk) for n in lengths]
    m = max(nb_per)
    total = sum(nb_per)
    nb = total + 1 + extra_blocks  # + garbage page 0
    q = jnp.asarray(rng.randn(bsz, h, d), jnp.float32)
    kp = jnp.asarray(rng.randn(nb, blk, kh, d), jnp.float32)
    vp = jnp.asarray(rng.randn(nb, blk, kh, d), jnp.float32)
    ids = rng.permutation(np.arange(1, nb))[:total]
    bt = np.zeros((bsz, m), np.int32)
    off = 0
    for r, n in enumerate(nb_per):
        bt[r, :n] = ids[off:off + n]
        off += n
    return q, kp, vp, jnp.asarray(bt)


def _dense_ref_rows(q, kp, vp, bt, lengths):
    """Row-by-row oracle via models.common.decode_attention (the dense
    public entry) over each request's gathered pages at its own position."""
    blk, kh, d = kp.shape[1], kp.shape[2], kp.shape[3]
    m = bt.shape[1]
    zeros = jnp.zeros((1, 1, kh, d), q.dtype)
    outs = []
    for r, n in enumerate(lengths):
        k = kp[bt[r]].reshape(1, m * blk, kh, d)
        v = vp[bt[r]].reshape(1, m * blk, kh, d)
        o, _, _ = mc.decode_attention(NULL_CTX, q[r:r + 1, None], k, v,
                                      zeros, zeros, int(n) - 1, update=False)
        outs.append(o[:, 0])
    return jnp.concatenate(outs, axis=0)


def _as_stacked_layer(rng, pool):
    """One layer's [NB, blk, KH, D] pool as layer 1 of a lane-folded
    [2, NB, blk, KH*D] stack whose layer 0 is noise."""
    folded = pool.reshape(*pool.shape[:2], -1)
    noise = jnp.asarray(rng.randn(*folded.shape), folded.dtype)
    return jnp.stack([noise, folded])


def _kernel_on_layer(rng, q, kp, vp, bt, lengths, **kw):
    """The Pallas kernel on `kp`/`vp` as layer 1 of a stacked pool."""
    return paged_decode_attention(q, _as_stacked_layer(rng, kp),
                                  _as_stacked_layer(rng, vp), 1, bt,
                                  jnp.asarray(lengths, jnp.int32), **kw)


def test_paged_decode_matches_dense_on_ragged_lengths(rng):
    """One round, per-request lengths spanning 8x: kernel vs the dense
    models.common.decode_attention entry AND the kernel's own ref oracle."""
    lengths = [16, 40, 128]  # 8x spread within one round
    q, kp, vp, bt = _paged_problem(rng, lengths, h=8, kh=2, d=16, blk=16)
    lens = jnp.asarray(lengths, jnp.int32)
    out = _kernel_on_layer(rng, q, kp, vp, bt, lengths)
    ref = _dense_ref_rows(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    oracle = paged_decode_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_paged_jnp_twin_matches_dense_on_ragged_lengths(rng):
    lengths = [4, 27, 64]
    q, kp, vp, bt = _paged_problem(rng, lengths, h=4, kh=4, d=8, blk=8)
    out = mc.paged_decode_attention(q[:, None], _as_stacked_layer(rng, kp),
                                    _as_stacked_layer(rng, vp), 1, bt,
                                    jnp.asarray(lengths, jnp.int32))[:, 0]
    ref = _dense_ref_rows(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_explicit_depth_and_padding_rows(rng):
    """Depth sweep + a zero-length padding slot pointing at the garbage
    page: real rows stay exact, the padding row is finite."""
    lengths = [32, 8, 0]
    q, kp, vp, bt = _paged_problem(rng, lengths, h=4, kh=2, d=16, blk=8)
    bt = bt.at[2].set(0)  # padding slot: all garbage page
    ref = _dense_ref_rows(q, kp, vp, bt, lengths[:2])
    for depth in (1, 2, 5):
        out = _kernel_on_layer(rng, q, kp, vp, bt, lengths, depth=depth)
        np.testing.assert_allclose(np.asarray(out[:2]), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
@pytest.mark.parametrize("kh,h,blk", [(2, 8, 16), (1, 4, 32), (4, 4, 8)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_decode_ragged_sweep(kh, h, blk, seed):
    """Long ragged-parity sweep: random length mixes with >=4x spread."""
    rng = np.random.RandomState(100 + seed)
    base = int(rng.randint(1, 2 * blk))
    lengths = sorted(rng.randint(base, 8 * base + 1, size=4).tolist())
    lengths[0], lengths[-1] = base, max(lengths[-1], 4 * base)  # >=4x spread
    q, kp, vp, bt = _paged_problem(rng, lengths, h=h, kh=kh, d=16, blk=blk)
    out = _kernel_on_layer(rng, q, kp, vp, bt, lengths)
    ref = _dense_ref_rows(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# a round's lengths at blocks of 16 (8 pages, 128 keys, a tile): a padding
# row, a page's edge, a tile's edge and one past it, and a long row whose
# last tile is partial
_ROUND = [0, 16, 128, 129, 300]


def _stacked_round(seed, kh, d, g, lengths=_ROUND, n_layers=3, blk=16):
    """Random stacked, lane-folded pools [n_layers, NB, blk, KH*D] and
    disjoint shuffled tables for `lengths`, one table column to spare."""
    rng = np.random.RandomState(seed)
    pages = [-(-n // blk) for n in lengths]
    m = max(pages) + 1
    nb = sum(pages) + 4
    shape = (n_layers, nb, blk, kh * d)
    kp = jnp.asarray(rng.randn(*shape), jnp.float32)
    vp = jnp.asarray(rng.randn(*shape), jnp.float32)
    ids = rng.permutation(np.arange(1, nb))
    bt = np.zeros((len(lengths), m), np.int32)
    off = 0
    for r, n in enumerate(pages):
        bt[r, :n] = ids[off:off + n]
        off += n
    q = jnp.asarray(rng.randn(len(lengths), kh * g, d), jnp.float32)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths, jnp.int32)


def _twin(q, kp, vp, layer, bt, lens, window):
    return mc.paged_decode_twin(q[:, None], kp, vp, layer, bt, lens,
                                window)[:, 0]


_HEADS = pytest.mark.parametrize("kh,d,g", [(8, 64, 2), (4, 128, 8)],
                                 ids=["kh8-d64-g2", "kh4-d128-g8"])
# 40 keys from 300 start mid-block (260 = 16 x 16 + 4); 24 from 129 too
_WINDOWS = pytest.mark.parametrize("window", [0, 40, 24],
                                   ids=["full", "window40", "window24"])


@_HEADS
@_WINDOWS
def test_paged_kernel_matches_twin_on_a_ragged_round(kh, d, g, window):
    """A round with a padding row and rows at a page's and a tile's edge,
    full or windowed (windows that start mid-block): the kernel's rows
    equal the twin's; the padding row reads nothing and gives zeros."""
    q, kp, vp, bt, lens = _stacked_round(7, kh, d, g)
    out = paged_decode_attention(q, kp, vp, 2, bt, lens, window=window)
    want = _twin(q, kp, vp, 2, bt, lens, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out)[0], 0.0)


@pytest.mark.parametrize("window", [0, 40], ids=["full", "window40"])
def test_paged_kernel_reads_the_stack_by_a_traced_layer(window):
    """Inside a jitted `lax.scan` over the layers, the traced layer index
    picks each layer of the stacked pools, read in place."""
    q, kp, vp, bt, lens = _stacked_round(8, 4, 32, 2)

    @jax.jit
    def all_layers(q, kp, vp, bt, lens):
        def body(carry, layer):
            return carry, paged_decode_attention(q, kp, vp, layer, bt, lens,
                                                 window=window)
        return jax.lax.scan(body, 0, jnp.arange(kp.shape[0]))[1]

    outs = all_layers(q, kp, vp, bt, lens)
    for layer in range(kp.shape[0]):
        want = _twin(q, kp, vp, layer, bt, lens, window)
        np.testing.assert_allclose(np.asarray(outs[layer]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@_HEADS
@pytest.mark.parametrize("window", [0, 40], ids=["full", "window40"])
def test_paged_kernel_never_reads_pages_outside_a_row(kh, d, g, window):
    """Every page a row does not attend — past its end (the spare table
    column and the unused pool pages) or wholly before its window — holds
    NaN: the kernel's output is finite and equals the twin's on the same
    pool with those pages finite. (The parity sentinel is off: the twin
    gathers the whole padded table, so on the poisoned pool it reads them.)"""
    from repro.core import guard

    guard.set_parity("off")
    q, kp, vp, bt, lens = _stacked_round(9, kh, d, g)
    blk = kp.shape[2]
    table, n = np.asarray(bt), np.asarray(lens)
    used = set()
    for r in range(len(n)):
        lo = max(int(n[r]) - window, 0) if window else 0
        used.update(table[r, lo // blk:-(-int(n[r]) // blk)].tolist())
    bt = bt.at[:, -1].set(sorted(set(range(kp.shape[1])) - used)[0])
    poison = np.ones(kp.shape[1], bool)
    poison[sorted(used)] = False
    kp_nan = kp.at[:, poison].set(jnp.nan)
    vp_nan = vp.at[:, poison].set(jnp.nan)
    out = paged_decode_attention(q, kp_nan, vp_nan, 1, bt, lens, window=window)
    assert np.isfinite(np.asarray(out)).all()
    want = _twin(q, kp, vp, 1, bt, lens, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------- stacked pools, layer by layer


def _per_layer_reference(params, cfg, k_pools, v_pools, tables, tokens, pos,
                         valid):
    """The paged step one layer at a time over unstacked [NB, blk, KH, D]
    pools: slice layer l out, append each row with a Python loop (rows not
    `valid` to the garbage page 0), attend each row over its gathered pages
    with a dense masked softmax, write layer l back. tokens/pos/valid:
    [B, C]. Returns (hidden states after the last layer [B, C, d], k_pools,
    v_pools), both pools [L, NB, blk, KH, D]."""
    blk, kh, d = k_pools.shape[2], k_pools.shape[3], k_pools.shape[4]
    g = cfg.n_heads // kh
    x = mc.embed_lookup(params["embed"], jnp.asarray(tokens)).astype(
        jnp.dtype(cfg.dtype))
    posj = jnp.asarray(pos)
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        kp, vp = k_pools[layer], v_pools[layer]
        p = lp["attn"]
        h = mc.rms_norm(x, p["ln"], cfg.norm_eps)
        q, k, v = lm._project_qkv(p, h, cfg)
        q = mc.apply_rope(q, posj, cfg.rope_theta)
        k = mc.apply_rope(k, posj, cfg.rope_theta)
        for b in range(tokens.shape[0]):
            for i in range(tokens.shape[1]):
                bid = tables[b, pos[b, i] // blk] if valid[b, i] else 0
                kp = kp.at[bid, pos[b, i] % blk].set(k[b, i])
                vp = vp.at[bid, pos[b, i] % blk].set(v[b, i])
        rows = []
        for b in range(tokens.shape[0]):
            keys = jnp.repeat(kp[tables[b]].reshape(-1, kh, d), g, axis=1)
            vals = jnp.repeat(vp[tables[b]].reshape(-1, kh, d), g, axis=1)
            sc = jnp.einsum("ihd,shd->ihs", q[b], keys) * (d ** -0.5)
            seen = np.arange(keys.shape[0])[None, None, :] <= pos[b][:, None,
                                                                     None]
            w = jax.nn.softmax(jnp.where(seen, sc, mc.NEG_INF), axis=-1)
            rows.append(jnp.einsum("ihs,shd->ihd", w, vals))
        x = lm._paged_ffn(lp, x + lm._attn_out(p, jnp.stack(rows), cfg), cfg,
                          NULL_CTX)
        k_pools = k_pools.at[layer].set(kp)
        v_pools = v_pools.at[layer].set(vp)
    return x, k_pools, v_pools


def _stacked_problem(arch, scan_layers, seed):
    cfg = get_config(arch).reduced().replace(
        dtype="float32", param_dtype="float32", scan_layers=scan_layers)
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    shape = (cfg.n_layers, 12, 4, cfg.kv_heads, cfg.resolved_head_dim)
    k5 = jnp.asarray(rng.randn(*shape), jnp.float32)
    v5 = jnp.asarray(rng.randn(*shape), jnp.float32)
    return cfg, params, rng, k5, v5


def _folded(pools):
    return pools.reshape(*pools.shape[:3], -1)


def _assert_pools_match(got, want, tol):
    """Every page but the garbage page 0, where padding rows collide."""
    np.testing.assert_allclose(np.asarray(got)[:, 1:],
                               np.asarray(_folded(want))[:, 1:], **tol)


_PARITY_TOL = dict(rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scan", "unroll"])
@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "yi-6b"])
def test_stacked_decode_matches_per_layer_reference(arch, scan_layers):
    """A ragged decode round through the stacked, lane-folded pools updated
    in place gives the per-layer reference's logits and pools; two padding
    rows (length 0, table all page 0) write only the garbage page."""
    cfg, params, rng, k5, v5 = _stacked_problem(arch, scan_layers, 3)
    tables = np.array([[3, 7, 1], [5, 0, 0], [0, 0, 0], [9, 2, 0],
                       [0, 0, 0]], np.int32)
    lengths = np.array([9, 4, 0, 6, 0], np.int32)
    tokens = rng.randint(1, cfg.vocab, size=(5, 1)).astype(np.int32)
    real = lengths > 0
    logits, kp, vp = lm.decode_step_paged(
        params, _folded(k5), _folded(v5), jnp.asarray(tables),
        jnp.asarray(lengths), {"tokens": jnp.asarray(tokens)}, cfg)
    x, k_ref, v_ref = _per_layer_reference(
        params, cfg, k5, v5, tables, tokens, lengths[:, None],
        np.ones((5, 1), bool))
    x = mc.rms_norm(x, params["final_norm"], cfg.norm_eps)
    want = lm._unembed(params, x, cfg, NULL_CTX)
    np.testing.assert_allclose(np.asarray(logits)[real],
                               np.asarray(want)[real], **_PARITY_TOL)
    _assert_pools_match(kp, k_ref, _PARITY_TOL)
    _assert_pools_match(vp, v_ref, _PARITY_TOL)
    assert not np.array_equal(np.asarray(kp)[:, 0], np.asarray(_folded(k5))[:, 0])


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scan", "unroll"])
@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "yi-6b"])
def test_stacked_prefill_chunk_matches_per_layer_reference(arch, scan_layers):
    """A prefill chunk of 5 real tokens padded to 8, from position 6 across
    the boundary of blocks of 4, through the stacked pools: the per-layer
    reference's last logits and pools; its 3 padding rows write page 0."""
    cfg, params, rng, k5, v5 = _stacked_problem(arch, scan_layers, 4)
    table = np.array([[6, 10, 3, 0]], np.int32)
    start, n_valid, chunk = 6, 5, 8
    tokens = rng.randint(1, cfg.vocab, size=(1, chunk)).astype(np.int32)
    logits, kp, vp = lm.prefill_chunk_paged(
        params, _folded(k5), _folded(v5), jnp.asarray(table),
        jnp.int32(start), {"tokens": jnp.asarray(tokens)}, jnp.int32(n_valid),
        cfg)
    pos = start + np.arange(chunk)[None]
    x, k_ref, v_ref = _per_layer_reference(
        params, cfg, k5, v5, table, tokens, pos, np.arange(chunk)[None] < n_valid)
    last = mc.rms_norm(x[:, n_valid - 1:n_valid], params["final_norm"],
                       cfg.norm_eps)
    want = lm._unembed(params, last, cfg, NULL_CTX)[:, 0]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               **_PARITY_TOL)
    _assert_pools_match(kp, k_ref, _PARITY_TOL)
    _assert_pools_match(vp, v_ref, _PARITY_TOL)
    assert not np.array_equal(np.asarray(kp)[:, 0], np.asarray(_folded(k5))[:, 0])


# ------------------------------------------------------------------ engine


def _f32_cfg():
    return get_config("yi-6b").reduced().replace(dtype="float32",
                                                 param_dtype="float32")


def test_engine_matches_dense_generation():
    """One request through the paged engine equals the dense prefill +
    decode_step loop token-for-token (float32)."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(1)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = rng.integers(0, cfg.vocab, 12)
    gen = 6

    cache, logits = model.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, pad_to=12 + gen)
    tok = int(jnp.argmax(logits[0, -1]))
    dense = [tok]
    for _ in range(gen - 1):
        logits, cache = model.decode_step(
            params, cache, {"tokens": jnp.asarray([[tok]], jnp.int32)})
        tok = int(jnp.argmax(logits[0, -1]))
        dense.append(tok)

    eng = PagedServingEngine(cfg, block_size=4, num_blocks=16, params=params)
    rid = eng.submit(prompt, max_new_tokens=gen)
    stats = eng.run()
    assert eng.request(rid).generated == dense
    assert stats["completed"] == 1


def test_engine_oversubscribes_dense_footprint():
    """A fixed pool serves aggregate KV >= 2x its own capacity (i.e. >= 2x
    any dense [batch, max_len] carve-up of the same memory): completions
    free pages that later admissions reuse."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(2)
    blk, gen = 4, 5
    plens = [5, 17, 6, 15, 7, 13, 9, 16]
    blocks_per_req = -(-(max(plens) + gen) // blk)
    eng = PagedServingEngine(cfg, block_size=blk,
                             num_blocks=2 * blocks_per_req, max_in_flight=3)
    rids = [eng.submit(rng.integers(0, cfg.vocab, n), max_new_tokens=gen)
            for n in plens]
    stats = eng.run()  # run() checks pager invariants at drain
    assert stats["completed"] == len(plens)
    assert stats["aggregate_kv_tokens"] >= 2 * stats["pool_tokens"]
    for rid in rids:
        assert len(eng.request(rid).generated) == gen


def test_engine_preemption_under_pool_pressure():
    """A pool barely bigger than one request forces preemption; the evicted
    request still finishes with the full token count."""
    cfg = _f32_cfg()
    rng = np.random.default_rng(3)
    blk, gen = 4, 6
    plens = [10, 10, 10]
    blocks_per_req = -(-(max(plens) + gen) // blk)
    eng = PagedServingEngine(cfg, block_size=blk,
                             num_blocks=blocks_per_req + 2, max_in_flight=3)
    rids = [eng.submit(rng.integers(0, cfg.vocab, n), max_new_tokens=gen)
            for n in plens]
    stats = eng.run()
    assert stats["preemptions"] > 0
    assert stats["completed"] == len(plens)
    for rid in rids:
        assert len(eng.request(rid).generated) == gen


def test_engine_rejects_unservable_shapes():
    cfg = _f32_cfg()
    eng = PagedServingEngine(cfg, block_size=4, num_blocks=4)
    with pytest.raises(ValueError):
        eng.submit(np.arange(1, 9), max_new_tokens=64)  # 18 blocks > pool
    with pytest.raises(ValueError):
        eng.submit([], max_new_tokens=1)
    with pytest.raises(ValueError):
        PagedServingEngine(get_config("mamba2-130m").reduced(),
                           block_size=4, num_blocks=4)


def test_decode_kv_bytes_on_both_paths():
    """The twin gathers every row at the padded width (a windowed layer its
    window's blocks); the kernel reads each row's pages from its first
    attended position's block through its last, none past its end."""
    from repro.serve.engine import decode_kv_bytes

    granite = get_config("granite-moe-1b-a400m").replace(dtype="bfloat16")
    per_block = 2 * 16 * 8 * 64 * 2                   # K+V of a page, one layer
    assert decode_kv_bytes(granite, 4, 192, 16) == 4 * 24 * 192 * per_block
    # 1, 16 (a page's edge), 17, 128 (a tile's edge), 300 positions
    lens = [1, 16, 17, 128, 300]
    assert decode_kv_bytes(granite, 5, 192, 16, lens) == (
        24 * (1 + 1 + 2 + 8 + 19) * per_block)
    # 12 layers of window 2048 and 4 full ones, KH*D = 512 as in granite
    windowed = granite.replace(n_layers=16, sliding_window=2048,
                               full_attn_every=4)
    assert windowed.layer_windows.count(2048) == 12
    wide = decode_kv_bytes(windowed, 3, 512, 16)
    assert wide == 3 * (12 * 129 + 4 * 512) * per_block
    # 5000 positions: the window opens at 2952, inside block 184 of 313
    lens = [100, 2049, 5000]
    full = [7, 129, 313]
    win = [7, 129, 313 - 184]
    assert decode_kv_bytes(windowed, 3, 512, 16, lens) == (
        (4 * sum(full) + 12 * sum(win)) * per_block)


def test_engine_reckons_the_kernels_read_on_the_kernel_path(monkeypatch):
    """With the platform check saying "kernel", every `decode_round` span
    carries the live pages of its rows (padding rows attend one position,
    one page), not the padded width."""
    from repro.obs import trace as obs_trace
    from repro.serve import engine as engine_mod

    monkeypatch.setattr(engine_mod, "decode_kernel_on", lambda: True)
    cfg = _f32_cfg()
    obs_trace.reset()
    rng = np.random.default_rng(5)
    eng = PagedServingEngine(cfg, block_size=4, num_blocks=32, max_in_flight=4)
    for n in (3, 9, 14):
        eng.submit(rng.integers(0, cfg.vocab, n), max_new_tokens=6)
    eng.run()
    per_block = 2 * 4 * cfg.kv_heads * cfg.resolved_head_dim * 4
    spans = [e["args"] for e in obs_trace.get_tracer().events
             if e.get("name") == "decode_round" and e.get("ph") == "X"]
    assert spans
    for a in spans:
        pages = a["n_tiles"] + (eng.round_width - a["width"])
        assert a["kv_bytes"] == cfg.n_layers * pages * per_block
        assert a["kv_bytes"] < engine_mod.decode_kv_bytes(
            cfg, eng.round_width, a["table_width"], 4)
