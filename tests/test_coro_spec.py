"""Declarative `CoroSpec` substrate: derivation rules, edge cases, parity.

Covers the ISSUE-2 acceptance criteria:
  * scratch derivation — per-slot (depth, *tile) buffers for streams,
    classified shapes for context vars (private x depth, shared x 1);
  * `choose_depth` consuming the classified context bytes: a shared
    accumulator permits a strictly deeper pipeline than the all-private
    baseline;
  * `context.max_depth` never returns the old unbounded sentinel;
  * pipeline edge cases — depth > n_tiles clamping, depth <= 0 rejection,
    grid mode with n_tiles == 1 (warmup + epilogue drain on one step);
  * old-vs-new API numerical parity on every kernel family (seeded
    sweeps, no hypothesis): the declarative entry points match the same
    oracles the hand-rolled kernels matched, at explicit depths and at
    ``depth=None``.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.context import MAX_DEPTH, VarClass, VarSpec, max_depth, var
from repro.core.coro import CoroSpec, LoadStream, coro_loop
from repro.core.schedule import TileProfile
from repro.kernels.coro_gather.coro_gather import row_gather_spec
from repro.kernels.coro_gather.ops import coro_gather
from repro.kernels.coro_gather.ref import gather_ref
from repro.kernels.coro_scatter_add.ops import coro_scatter_add
from repro.kernels.coro_scatter_add.ref import scatter_add_ref
from repro.kernels.decode_attention.decode_attention import decode_spec
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import gmm_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_spec
from repro.kernels.stream_copy.ops import stream_triad
from repro.kernels.stream_copy.ref import triad_ref


# ------------------------------------------------------- spec derivation


def test_stream_slots_are_private_context():
    spec = row_gather_spec(8, 64, jnp.float32)
    sv = spec.stream_vars()
    assert [v.name for v in sv] == ["rows"]
    assert sv[0].nbytes == 8 * 64 * 4
    # a slot is rewritten every rotation from its own tile only -> private,
    # so context_bytes scales with depth
    assert spec.context_bytes(8) == 8 * spec.context_bytes(1)


def test_decode_spec_context_is_depth_independent_for_accumulators():
    spec = decode_spec(32, 2, 2, 16, jnp.float32)
    d1, d8 = spec.context_bytes(1), spec.context_bytes(8)
    slot_bytes = sum(s.nbytes for s in spec.loads)
    # only the k/v slots multiply by depth; m/l/acc/q stay x1
    assert d8 - d1 == 7 * slot_bytes
    # the all-private baseline (conventional coroutine frames) is strictly
    # larger at any depth > 1 — Fig. 15's context-minimization gain
    assert spec.context_bytes(8, baseline=True) > d8


def test_scratch_shapes_follow_classification():
    spec = ssd_spec(16, 2, 8, 16, jnp.float32, seq_len=64)
    shapes = spec.scratch_shapes(depth=5)
    # 4 load slots + 1 load semaphore + 1 materialized var (the h state)
    assert len(shapes) == 6
    # x slots: private x depth, heads folded into the lane dim (2 * 8)
    assert shapes[0].shape == (5, 16, 16)
    assert shapes[-1].shape == (2, 8, 16)         # h state: sequential x 1


def test_spec_rejects_duplicate_names():
    with pytest.raises(ValueError, match="duplicate"):
        CoroSpec(
            name="dup",
            loads=(LoadStream("a", (1, 1), jnp.float32, src=lambda c, t: None),),
            vars=(VarSpec("a", 4),),
        )


def test_stream_rejects_indivisible_group():
    # tile[0]=10 over group=4 would silently truncate to 8 rows per slot
    with pytest.raises(ValueError, match="group"):
        LoadStream("rows", (10, 4), jnp.float32, src=lambda c, t: [], group=4)


def test_last_choice_reports_clamped_depth(rng):
    """The recorded auto-depth is the one the kernel ran with, never the
    solver's raw (possibly > n_tiles, unallocatable) answer."""
    table = jnp.asarray(rng.randn(64, 16), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 64, 16), jnp.int32)  # 2 tiles
    coro_gather(table, idx)  # solver wants far more than 2 slots
    assert autotune.last_choice("row_gather") == 2


def test_var_helper_derives_nbytes():
    v = var("h", (2, 8, 16), jnp.float32, carries_dependence=True)
    assert v.nbytes == 2 * 8 * 16 * 4
    assert v.shape == (2, 8, 16)


# ------------------------------------------- classified VMEM cap in autotune


def test_shared_accumulator_permits_deeper_pipeline():
    """The ISSUE-2 criterion: choose_depth(vars=...) caps from classified
    context bytes, so a commutative (shared) accumulator reaches a strictly
    deeper pipeline than the same bytes classified private."""
    slot = VarSpec("slot", 1 << 20)  # the stream slot: private
    acc = VarSpec("acc", 1 << 20, carries_dependence=True, commutative=True)
    acc_private = dataclasses.replace(acc, hint=VarClass.PRIVATE)
    profile = TileProfile(tile_bytes=1 << 20, flops_per_tile=1.0)
    budget = 8 << 20
    kw = dict(latency_s=20e-6, vmem_budget=budget)
    d_shared = autotune.choose_depth(profile, vars=[slot, acc], **kw)
    d_private = autotune.choose_depth(profile, vars=[slot, acc_private], **kw)
    assert d_shared > d_private
    assert d_shared == 7   # (8MB - 1MB shared) // 1MB per slot
    assert d_private == 4  # 8MB // 2MB per slot


def test_max_depth_sentinel_is_clamped():
    # all-shared context: no per-slot bytes — the old code returned 2**30
    vs = [VarSpec("ro", 64, read_only=True)]
    assert max_depth(vs, 1 << 20) == MAX_DEPTH
    assert max_depth(vs, 1) == 0  # shared alone overflows the budget
    # and the general case is request-slot capped too
    vs = [VarSpec("tiny", 1)]
    assert max_depth(vs, 1 << 30) == MAX_DEPTH


# ----------------------------------------------------- pipeline edge cases


def test_coro_loop_nonpositive_depth_is_noop():
    called = []
    out = coro_loop(4, 0, called.append, lambda t, s, c: c, called.append,
                    carry_init=7)
    assert out == 7 and not called


@pytest.mark.parametrize("bad", [0, -3])
def test_entry_points_reject_nonpositive_depth(rng, bad):
    table = jnp.asarray(rng.randn(32, 16), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 32, 8), jnp.int32)
    with pytest.raises(ValueError, match="depth"):
        coro_gather(table, idx, depth=bad)
    b = jnp.asarray(rng.randn(64, 8), jnp.float32)
    with pytest.raises(ValueError, match="depth"):
        stream_triad(b, b, 1.0, rows=32, depth=bad)


def test_depth_exceeding_n_tiles_is_clamped(rng):
    table = jnp.asarray(rng.randn(64, 16), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 64, 16), jnp.int32)  # 2 tiles
    out = coro_gather(table, idx, depth=64)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gather_ref(table, idx)))
    b = jnp.asarray(rng.randn(64, 8), jnp.float32)
    c = jnp.asarray(rng.randn(64, 8), jnp.float32)
    out = stream_triad(b, c, 2.0, rows=32, depth=50)  # 2 tiles
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(triad_ref(b, c, 2.0)),
                               rtol=1e-5, atol=1e-5)


def test_grid_mode_single_tile(rng):
    """n_tiles == 1: warmup, consume, store issue and epilogue drain all on
    the one grid step."""
    table = jnp.asarray(rng.randn(32, 16), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 32, 8), jnp.int32)
    out = coro_gather(table, idx)  # 8 idx / rows_per_tile 8 -> 1 tile
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gather_ref(table, idx)))

    b = jnp.asarray(rng.randn(32, 8), jnp.float32)
    c = jnp.asarray(rng.randn(32, 8), jnp.float32)
    out = stream_triad(b, c, 1.5, rows=32)  # n == rows -> 1 tile
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(triad_ref(b, c, 1.5)),
                               rtol=1e-5, atol=1e-5)

    uniq = np.asarray(rng.permutation(32)[:8], np.int32)  # 1 RMW tile
    upd = jnp.asarray(rng.randn(8, 16), jnp.float32)
    out = coro_scatter_add(table, uniq, upd)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(scatter_add_ref(table, uniq, upd)),
        rtol=1e-6, atol=1e-6)


def test_fori_mode_single_tile(rng):
    q = jnp.asarray(rng.randn(1, 4, 16), jnp.float32)
    kv = jnp.asarray(rng.randn(1, 32, 2, 16), jnp.float32)
    out = decode_attention(q, kv, kv, 20, blk=32)  # s == blk -> 1 block
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(decode_attention_ref(q, kv, kv, 20)),
        rtol=2e-5, atol=2e-5)


# ------------------------------------------------ parity: all six families
#
# The hand-rolled kernels matched these oracles before the CoroSpec port;
# the declarative entry points must match them identically, both at swept
# explicit depths and with the autotuned depth=None.


@pytest.mark.parametrize("depth", [1, 2, 5, None])
def test_parity_row_gather(rng, depth):
    table = jnp.asarray(rng.randn(96, 32) * 5, jnp.float32)
    idx = jnp.asarray(rng.randint(0, 96, 40), jnp.int32)
    out = coro_gather(table, idx, depth=depth)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gather_ref(table, idx)))


@pytest.mark.parametrize("depth", [1, 3, None])
def test_parity_scatter_add(rng, depth):
    table = jnp.asarray(rng.randn(48, 16), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 48, 30), jnp.int32)
    upd = jnp.asarray(rng.randn(30, 16), jnp.float32)
    out = coro_scatter_add(table, idx, upd, depth=depth)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(scatter_add_ref(table, idx, upd)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("depth", [1, 2, None])
def test_parity_decode_attention(rng, depth):
    q = jnp.asarray(rng.randn(2, 4, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 128, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 128, 2, 16), jnp.float32)
    out = decode_attention(q, k, v, 97, blk=32, depth=depth)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(decode_attention_ref(q, k, v, 97)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("depth", [1, 2, None])
def test_parity_moe_gmm(rng, depth):
    t = jnp.asarray(rng.randn(2, 8, 16), jnp.float32)
    w = jnp.asarray(rng.randn(2, 16, 256), jnp.float32)
    out = moe_gmm(t, w, f_tile=64, depth=depth)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gmm_ref(t, w)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("depth", [1, 2, None])
def test_parity_ssd(rng, depth):
    x = jnp.asarray(rng.randn(1, 64, 2, 8), jnp.float32)
    dt = jnp.asarray(rng.rand(1, 64, 2) * 0.5 + 0.1, jnp.float32)
    A = jnp.asarray(-np.exp(rng.randn(2) * 0.3), jnp.float32)
    B = jnp.asarray(rng.randn(1, 64, 16), jnp.float32)
    C = jnp.asarray(rng.randn(1, 64, 16), jnp.float32)
    y, hf = ssd(x, dt, A, B, C, chunk=16, depth=depth)
    yr, hr = ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("depth", [1, 4, None])
def test_parity_triad(rng, depth):
    b = jnp.asarray(rng.randn(256, 16), jnp.float32)
    c = jnp.asarray(rng.randn(256, 16), jnp.float32)
    out = stream_triad(b, c, 3.0, rows=64, depth=depth)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(triad_ref(b, c, 3.0)),
                               rtol=1e-5, atol=1e-5)
