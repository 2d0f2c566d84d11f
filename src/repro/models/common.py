"""Layer library: norms, RoPE, GQA attention (naive / chunked / flash-decode),
SwiGLU MLP, embeddings.

Attention comes in three implementations:
  * naive    — materializes [B,H,Sq,Sk] scores. Paper-faithful baseline.
  * chunked  — online-softmax scan over KV chunks (flash-style in jnp). This is
               the jnp twin of the coroutine pipeline: each KV chunk is one
               in-flight "coroutine" tile; see kernels/decode_attention for the
               Pallas version with real decoupled DMA.
  * flash-decode (shard_map) — sequence-sharded KV cache (context parallelism)
               with partial-softmax psum combine over the model axis.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import ShardingCtx

# --------------------------------------------------------------------- basics


def _rms(x, eps):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)


def rms_norm(x, w, eps: float = 1e-5):
    return (_rms(x, eps) * w.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions: [S] or [B, S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # [D/2]
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, D/2]
    # broadcast over heads axis
    angles = angles[..., None, :]  # [..., S, 1, D/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoid_pos_emb(seq: int, d_model: int, dtype):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-jnp.log(10_000.0) * dim / d_model)
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate.astype(x.dtype)) * (x @ w_up.astype(x.dtype))
    return h @ w_down.astype(x.dtype)


def embed_lookup(table, tokens):
    """Embedding gather — the GUPS/hash-join access pattern of the paper.

    On TPU the kernels/coro_gather pipeline implements this with decoupled
    DMA; the jnp `take` is the oracle-equivalent used on CPU and in dry-runs.
    """
    return jnp.take(table, tokens, axis=0)


# ------------------------------------------------------------------ attention


def _mask(q_pos, k_pos, *, causal: bool, window: int, prefix: int):
    """q_pos: [Sq,1] int32, k_pos: [1,Sk] int32 -> bool [Sq,Sk] (True=keep)."""
    if not causal:
        return jnp.ones((q_pos.shape[0], k_pos.shape[1]), bool)
    ok = k_pos <= q_pos
    if window:
        ok &= k_pos > (q_pos - window)
    if prefix:
        ok |= k_pos < prefix
    return ok


def _group(q, kv_heads: int):
    """[B,S,H,D] -> [B,S,KH,G,D] grouped-query layout (no KV repeat)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, kv_heads, h // kv_heads, d)


NEG_INF = -1e30


def attention_naive(q, k, v, *, q_pos, k_pos, causal=True, window=0, prefix=0):
    """Materialized-scores attention. [B,Sq,H,D] x [B,Sk,KH,D] -> [B,Sq,H,D]."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    qg = _group(q, kh) * (d ** -0.5)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    m = _mask(q_pos[:, None], k_pos[None, :], causal=causal, window=window, prefix=prefix)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o.reshape(b, sq, h, d)


def attention_chunked(q, k, v, *, q_pos, k_pos, causal=True, window=0, prefix=0,
                      chunk=1024, unroll=False):
    """Online-softmax scan over KV chunks (memory O(Sq*chunk) instead of Sq*Sk)."""
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    if sk % chunk != 0 or sk <= chunk:
        return attention_naive(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                               window=window, prefix=prefix)
    n_chunks = sk // chunk
    qg = (_group(q, kh) * (d ** -0.5)).astype(q.dtype)
    ks = k.reshape(b, n_chunks, chunk, kh, d).swapaxes(0, 1)
    vs = v.reshape(b, n_chunks, chunk, kh, d).swapaxes(0, 1)
    kp = k_pos.reshape(n_chunks, chunk)

    g = h // kh
    acc0 = jnp.zeros((b, kh, g, sq, d), jnp.float32)
    m0 = jnp.full((b, kh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, sq), jnp.float32)

    def body(carry, xs):
        acc, m, l = carry
        kc, vc, kpc = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc, preferred_element_type=jnp.float32)
        msk = _mask(q_pos[:, None], kpc[None, :], causal=causal, window=window, prefix=prefix)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(q.dtype), vc).astype(jnp.float32)
        acc = acc * corr[..., None] + pv
        return (acc, m_new, l), None

    if unroll:  # dry-run exact accounting: Python loop instead of lax.scan
        carry = (acc0, m0, l0)
        for i in range(n_chunks):
            carry, _ = body(carry, (ks[i], vs[i], kp[i]))
        acc, _, l = carry
    else:
        (acc, _, l), _ = jax.lax.scan(body, (acc0, m0, l0), (ks, vs, kp))
    o = acc / jnp.maximum(l[..., None], 1e-30)  # [b,kh,g,sq,d]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, kh * g, d).astype(q.dtype)


def attention_swa_block(q, k, v, *, q_pos, window: int, chunk: int):
    """Block-local sliding-window attention (§Perf): each query chunk attends
    only to its own and the previous key chunk — O(S*2c) score work instead
    of O(S*S_kv). Requires window <= chunk, self-attention, s % chunk == 0."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    nc = s // chunk
    qc = (_group(q, kh) * (d ** -0.5)).reshape(b, nc, chunk, kh, g, d)
    kc = k.reshape(b, nc, chunk, kh, d)
    vc = v.reshape(b, nc, chunk, kh, d)
    kprev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    kk = jnp.concatenate([kprev, kc], axis=2)  # [b,nc,2c,kh,d]
    vv = jnp.concatenate([vprev, vc], axis=2)
    s_ = jnp.einsum("bcqkgd,bcskd->bckgqs", qc, kk,
                    preferred_element_type=jnp.float32)
    qp = q_pos.reshape(nc, chunk)
    kp = jnp.concatenate([qp - chunk, qp], axis=1)  # [nc, 2c]
    msk = (kp[:, None, :] <= qp[:, :, None]) & \
          (kp[:, None, :] > qp[:, :, None] - window) & (kp[:, None, :] >= 0)
    s_ = jnp.where(msk[None, :, None, None], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1).astype(q.dtype)
    o = jnp.einsum("bckgqs,bcskd->bcqkgd", p, vv)
    return o.reshape(b, s, h, d)


def attention(q, k, v, *, q_pos, k_pos, causal=True, window=0, prefix=0,
              impl="auto", chunk=1024, unroll=False):
    s_q, s_kv = q.shape[1], k.shape[1]
    if impl == "swa_block" or (
        impl == "auto" and causal and window and not prefix
        and s_q == s_kv and window <= chunk and s_q % chunk == 0
        and s_q >= 2 * chunk
    ):
        return attention_swa_block(q, k, v, q_pos=q_pos, window=window,
                                   chunk=max(window, chunk if s_q % chunk == 0 else window))
    if impl == "naive" or (impl == "auto" and s_kv <= max(chunk, 4096)):
        return attention_naive(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                               window=window, prefix=prefix)
    return attention_chunked(q, k, v, q_pos=q_pos, k_pos=k_pos, causal=causal,
                             window=window, prefix=prefix, chunk=chunk,
                             unroll=unroll)


# ----------------------------------------------------- flash-decode (sharded)


def _row_update(cache, new_row, safe, in_range):
    """Row-granular cache write: read 1 row, select, write 1 row — instead of
    a full-cache where() copy (§Perf: cuts decode cache traffic ~3x)."""
    b, _, kh, d = new_row.shape
    old = jax.lax.dynamic_slice(cache, (0, safe, 0, 0), (cache.shape[0], 1, kh, d))
    row = jnp.where(in_range, new_row.astype(cache.dtype), old)
    return jax.lax.dynamic_update_slice(cache, row, (0, safe, 0, 0))


def _decode_core(q, k_cache, v_cache, new_k, new_v, pos, *, s_local, model_axis,
                 update=True, update_mode="full"):
    """Manual (shard_map) decode-attention body. Shapes are per-shard:

      q:        [B, 1, H, D]   (replicated over model axis)
      k_cache:  [B, S_l, KH, D] (sequence-sharded over model axis)
      new_k/v:  [B, 1, KH, D]
      pos:      [] int32 — current decode position (cache valid in [0, pos])
    Returns (out [B,1,H,D] replicated, updated k_cache, v_cache).
    """
    b, _, h, d = q.shape
    kh = k_cache.shape[2]
    idx = jax.lax.axis_index(model_axis)
    offset = idx * s_local
    if update:
        # ---- cache update (write lands on exactly one shard)
        local = pos - offset
        in_range = (local >= 0) & (local < s_local)
        safe = jnp.clip(local, 0, s_local - 1)
        if update_mode == "row":
            k_cache = _row_update(k_cache, new_k, safe, in_range)
            v_cache = _row_update(v_cache, new_v, safe, in_range)
        else:
            upd_k = jax.lax.dynamic_update_slice(k_cache, new_k.astype(k_cache.dtype), (0, safe, 0, 0))
            upd_v = jax.lax.dynamic_update_slice(v_cache, new_v.astype(v_cache.dtype), (0, safe, 0, 0))
            k_cache = jnp.where(in_range, upd_k, k_cache)
            v_cache = jnp.where(in_range, upd_v, v_cache)
    # ---- partial attention over the local KV slice
    qg = _group(q, kh)[:, 0] * (d ** -0.5)  # [B,KH,G,D]
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32)
    k_pos = offset + jnp.arange(s_local)
    valid = k_pos[None, None, None, :] <= pos
    s = jnp.where(valid, s, NEG_INF)
    m = s.max(axis=-1)  # [B,KH,G]
    m_g = jax.lax.pmax(m, model_axis)
    p = jnp.exp(s - m_g[..., None])
    num = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v_cache).astype(jnp.float32)
    den = p.sum(axis=-1)
    num = jax.lax.psum(num, model_axis)
    den = jax.lax.psum(den, model_axis)
    o = (num / jnp.maximum(den[..., None], 1e-30)).astype(q.dtype)
    return o.reshape(b, 1, h, d), k_cache, v_cache


def flash_decode_attention(ctx: ShardingCtx, q, k_cache, v_cache, new_k, new_v, pos,
                           update=True, update_mode="full"):
    """Sequence-sharded decode attention (context parallelism over `model`).

    Falls back to a single-shard jnp path when no mesh is present.
    """
    s_total = k_cache.shape[1]
    if ctx.mesh is None or "model" not in ctx.axis_sizes or not ctx.use_shard_map:
        return _single_decode(q, k_cache, v_cache, new_k, new_v, pos, update)
    n_model = ctx.axis_sizes["model"]
    if s_total % n_model != 0:
        return _single_decode(q, k_cache, v_cache, new_k, new_v, pos, update)
    s_local = s_total // n_model

    mesh = ctx.mesh
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    q_s = P(bspec, None, None, None)
    cache_s = P(bspec, "model", None, None)
    new_s = P(bspec, None, None, None)

    fn = functools.partial(_decode_core, s_local=s_local, model_axis="model",
                           update=update, update_mode=update_mode)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(q_s, cache_s, cache_s, new_s, new_s, P()),
        out_specs=(q_s, cache_s, cache_s),
        check_vma=False,
    )(q, k_cache, v_cache, new_k, new_v, pos)


def _single_decode(q, k_cache, v_cache, new_k, new_v, pos, update=True):
    """Unsharded decode attention (CPU smoke tests)."""
    if update:
        k_cache = jax.lax.dynamic_update_slice(k_cache, new_k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, new_v.astype(v_cache.dtype), (0, pos, 0, 0))
    b, _, h, d = q.shape
    kh = k_cache.shape[2]
    qg = _group(q, kh)[:, 0] * (d ** -0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32)
    valid = jnp.arange(k_cache.shape[1])[None, None, None, :] <= pos
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v_cache)
    return o.reshape(b, 1, h, d), k_cache, v_cache


def decode_attention(ctx: ShardingCtx, q, k_cache, v_cache, new_k, new_v, pos,
                     update=True, update_mode="full"):
    """Public decode-attention entry: sharded flash-decode when a mesh exists."""
    if ctx.mesh is None:
        return _single_decode(q, k_cache, v_cache, new_k, new_v, pos, update)
    return flash_decode_attention(ctx, q, k_cache, v_cache, new_k, new_v, pos,
                                  update, update_mode)


# --------------------------------------------------------- paged KV (serving)
#
# The serving engine keeps every layer's KV blocks in one stacked pool per
# tensor, [L, NB, blk, KH*D]: heads folded into the lane axis, so each
# (layer, block, offset) row is one contiguous KH*D vector. The helpers take
# the whole stack and a layer index, scatter new rows at [layer, block,
# offset] and gather [layer, block_tables], so a layer loop that carries the
# stack updates it in place and never slices a layer's pool out.


def window_blocks(window: int, block_size: int, table_width: int) -> int:
    """Blocks a windowed layer's decode gathers per row: the fewest that
    always hold the last `window` positions, ceil(window / blk) + 1 (a
    window starting mid-block spans one more), or the whole table where
    that is narrower."""
    return min(-(-window // block_size) + 1, table_width)


def window_tables(block_tables, lengths, window: int, block_size: int):
    """Each row's blocks that hold its last `window` of `lengths` positions:
    ([B, window_blocks] block ids, [B] logical index of the first one).
    The slice starts at the block of position lengths - window (or 0) and
    is clamped to end inside the table."""
    m = block_tables.shape[1]
    n = window_blocks(window, block_size, m)
    first = jnp.clip((lengths - window) // block_size, 0, m - n)
    cols = first[:, None] + jnp.arange(n, dtype=first.dtype)
    return jnp.take_along_axis(block_tables, cols, axis=1), first


def _gather_layer_pages(k_pools, v_pools, layer, block_tables, d):
    """Gather `block_tables`' pages of one layer and unfold the heads:
    [B, M] block ids -> k, v [B, M*blk, KH, D]."""
    b, m = block_tables.shape
    blk, lanes = k_pools.shape[2], k_pools.shape[3]
    shape = (b, m * blk, lanes // d, d)
    return (k_pools[layer, block_tables].reshape(shape),
            v_pools[layer, block_tables].reshape(shape))


def paged_cache_append(k_pools, v_pools, layer, block_tables, lengths, new_k,
                       new_v):
    """Write one new KV row per request into its paged block of one layer.

    k_pools/v_pools: [L, NB, blk, KH*D] the stacked, lane-folded pools;
    layer: [] int32 (or a Python int) layer index; block_tables: [B, M]
    int32 block ids; lengths: [B] int32 tokens already stored — row b
    lands at `[layer, block_tables[b, lengths[b] // blk], lengths[b] %
    blk]`. new_k/new_v: [B, 1, KH, D]. Requests own disjoint blocks
    (serve.kv_pager invariant) so the scatter indices never collide,
    except padding rows which all target the reserved garbage block 0.
    Returns the updated (k_pools, v_pools).
    """
    b = lengths.shape[0]
    blk = k_pools.shape[2]
    bids = block_tables[jnp.arange(b), lengths // blk]
    offs = lengths % blk
    rows = (b, k_pools.shape[3])
    k_pools = k_pools.at[layer, bids, offs].set(
        new_k.reshape(rows).astype(k_pools.dtype))
    v_pools = v_pools.at[layer, bids, offs].set(
        new_v.reshape(rows).astype(v_pools.dtype))
    return k_pools, v_pools


def paged_cache_append_chunk(k_pools, v_pools, layer, block_tables, start,
                             new_k, new_v, n_valid):
    """Write a chunk of consecutive KV rows into one layer's paged blocks.

    The chunked-prefill analogue of `paged_cache_append`: rows
    ``i < n_valid`` of the (right-padded) chunk land at logical positions
    ``start + i`` through the block table; padding rows are redirected to
    the reserved garbage block 0 so they never clobber real pages.

    k_pools/v_pools: [L, NB, blk, KH*D]; layer: [] int32 (or a Python int);
    block_tables: [B, M] int32; start/n_valid: [] int32 (one request per
    call — chunks are per-request); new_k/new_v: [B, C, KH, D].
    """
    b, c = new_k.shape[0], new_k.shape[1]
    blk = k_pools.shape[2]
    m = block_tables.shape[1]
    idx = jnp.arange(c, dtype=jnp.int32)
    pos = start + idx                                   # [C] absolute positions
    bi = jnp.minimum(pos // blk, m - 1)
    bids = block_tables[:, bi]                          # [B, C]
    valid = (idx < n_valid) & (pos // blk < m)
    bids = jnp.where(valid[None, :], bids, 0)           # padding -> garbage
    offs = jnp.broadcast_to(pos % blk, (b, c))
    rows = (b, c, k_pools.shape[3])
    k_pools = k_pools.at[layer, bids, offs].set(
        new_k.reshape(rows).astype(k_pools.dtype))
    v_pools = v_pools.at[layer, bids, offs].set(
        new_v.reshape(rows).astype(v_pools.dtype))
    return k_pools, v_pools


def paged_prefill_attention(q, k_pools, v_pools, layer, block_tables, q_pos,
                            window: int = 0):
    """Causal chunk attention against one layer of a paged KV cache
    (chunked prefill).

    q: [B, C, H, D] — a chunk of prompt queries at absolute positions
    `q_pos` [B, C]; k_pools/v_pools: [L, NB, blk, KH*D]; layer: [] int32
    (or a Python int); block_tables: [B, M] int32 (padded with the garbage
    block 0). Query i attends every pool position <= q_pos[b, i] — its own
    chunk's rows were appended first (`paged_cache_append_chunk`), earlier
    rows hold the already-prefilled (or prefix-cache-shared) prefix. A
    windowed layer (`window` > 0) keeps only the keys of the last
    `window` positions, q_pos - window < j <= q_pos.
    Returns [B, C, H, D]; padded query rows produce garbage the caller
    discards.
    """
    b, c, h, d = q.shape
    k, v = _gather_layer_pages(k_pools, v_pools, layer, block_tables, d)
    s_len, kh = k.shape[1], k.shape[2]
    qg = _group(q, kh) * (d ** -0.5)                   # [B,C,KH,G,D]
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    k_pos = jnp.arange(s_len)[None, None, :]
    valid = k_pos <= q_pos[:, :, None]                 # [B,C,S]
    if window:
        valid &= k_pos > q_pos[:, :, None] - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return o.reshape(b, c, h, d)


def paged_decode_attention(q, k_pools, v_pools, layer, block_tables, lengths,
                           window: int = 0):
    """Decode attention over one layer of a paged KV cache (jnp twin of the
    Pallas `kernels/decode_attention.paged_flash_decode`).

    q: [B, 1, H, D]; k_pools/v_pools: [L, NB, blk, KH*D]; layer: [] int32
    (or a Python int); block_tables: [B, M] int32 (padded with the garbage
    block 0); lengths: [B] int32 — request b attends key positions <
    lengths[b]. A windowed layer (`window` > 0) attends only positions
    >= lengths[b] - window, and gathers only the `window_blocks` that hold
    them, from each row's own place in its table. Returns [B, 1, H, D].
    Rows with lengths == 0 (padding slots in a round) produce garbage the
    caller discards.
    """
    b, _, h, d = q.shape
    k_pos = jnp.arange(block_tables.shape[1] * k_pools.shape[2])[None]   # [1,S]
    if window:
        blk = k_pools.shape[2]
        block_tables, first = window_tables(block_tables, lengths, window, blk)
        k_pos = first[:, None] * blk + jnp.arange(block_tables.shape[1] * blk)
    k, v = _gather_layer_pages(k_pools, v_pools, layer, block_tables, d)
    kh = k.shape[2]
    qg = _group(q, kh)[:, 0] * (d ** -0.5)  # [B,KH,G,D]
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k, preferred_element_type=jnp.float32)
    valid = k_pos < lengths[:, None]
    if window:
        valid &= k_pos >= lengths[:, None] - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v)
    return o.reshape(b, 1, h, d)
