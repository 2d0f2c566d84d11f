"""Unified LM: dense / MoE / SSM / hybrid / enc-dec / VLM from one block set.

Step kinds:
  loss(params, batch)                 - training forward (full seq, causal)
  prefill(params, batch) -> cache     - one-shot prefill building KV caches
  decode_step(params, cache, batch)   - one new token per sequence

Layer stacking is `lax.scan` for the real paths and a Python unroll for
dry-runs (`cfg.scan_layers=False`) so XLA cost analysis counts every layer
(see DESIGN.md §3.2).
"""
from __future__ import annotations

import functools
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeSuite, cache_seq_len, token_split
from repro.models import params as pm
from repro.models.common import (
    NEG_INF,
    apply_rope,
    attention,
    decode_attention,
    embed_lookup,
    paged_cache_append,
    paged_cache_append_chunk,
    paged_decode_attention,
    paged_prefill_attention,
    rms_norm,
    sinusoid_pos_emb,
    swiglu,
)
from repro.models.moe import moe_layer, moe_param_specs
from repro.models.ssm import (
    ssm_cache_shape,
    ssm_decode,
    ssm_forward,
    ssm_param_specs,
)
from repro.models.params import ParamSpec
from repro.sharding import NULL_CTX, ShardingCtx

# ------------------------------------------------------------- param specs


def _attn_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.resolved_head_dim
    s: Dict[str, ParamSpec] = {
        "ln": ParamSpec((d,), ("embed",), init="ones"),
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"), init="fan_in"),
        "wk": ParamSpec((d, kh, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wv": ParamSpec((d, kh, hd), ("embed", "kv_heads", "head_dim"), init="fan_in"),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"), init="fan_in"),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
        s["k_norm"] = ParamSpec((hd,), ("head_dim",), init="ones")
    if cfg.attn_gate:
        s["w_gate"] = ParamSpec((d, h, hd), ("embed", "heads", "head_dim"), init="fan_in")
    if cfg.sandwich_norm:
        s["post_ln"] = ParamSpec((d,), ("embed",), init="ones")
    if cfg.use_bias:
        s["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros")
        s["bk"] = ParamSpec((kh, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bv"] = ParamSpec((kh, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bo"] = ParamSpec((d,), ("embed",), init="zeros")
    return s


def _mlp_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    s = {
        "ln": ParamSpec((d,), ("embed",), init="ones"),
        "w_gate": ParamSpec((d, f), ("embed", "mlp"), init="fan_in"),
        "w_up": ParamSpec((d, f), ("embed", "mlp"), init="fan_in"),
        "w_down": ParamSpec((f, d), ("mlp", "embed"), init="fan_in"),
    }
    if cfg.sandwich_norm:
        s["post_ln"] = ParamSpec((d,), ("embed",), init="ones")
    return s


def _layer_specs(cfg: ArchConfig, *, cross: bool = False, encoder: bool = False,
                 dense: bool = False) -> Dict[str, Any]:
    """One layer's parameters; `dense` is a leading SwiGLU layer of an MoE
    stack (`cfg.n_dense_layers`)."""
    s: Dict[str, Any] = {}
    if cfg.family == "ssm":
        s["ssm_ln"] = ParamSpec((cfg.d_model,), ("embed",), init="ones")
        s["ssm"] = ssm_param_specs(cfg)
        return s
    s["attn"] = _attn_specs(cfg)
    if cfg.hybrid:
        s["ssm"] = ssm_param_specs(cfg)
    if cross:
        s["cross"] = _attn_specs(cfg)
    if cfg.moe and not dense:
        s["moe"] = moe_param_specs(cfg)
        s["moe_ln"] = ParamSpec((cfg.d_model,), ("embed",), init="ones")
        if cfg.sandwich_norm:
            s["moe_post_ln"] = ParamSpec((cfg.d_model,), ("embed",), init="ones")
    else:
        s["mlp"] = _mlp_specs(cfg)
    return s


def _stack(tree, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.dtype, s.init, s.scale),
        tree,
        is_leaf=pm.is_spec,
    )


def param_specs(cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab, d), ("vocab", "embed")),
        "final_norm": ParamSpec((d,), ("embed",), init="ones"),
        "layers": _stack(_layer_specs(cfg, cross=cfg.enc_dec),
                         cfg.n_layers - cfg.n_dense_layers),
    }
    if cfg.n_dense_layers:
        specs["dense_layers"] = _stack(_layer_specs(cfg, dense=True),
                                       cfg.n_dense_layers)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((d, cfg.vocab), ("embed", "vocab"))
    if cfg.enc_dec:
        specs["enc_layers"] = _stack(
            _layer_specs(cfg, encoder=True), cfg.n_enc_layers
        )
        specs["enc_norm"] = ParamSpec((d,), ("embed",), init="ones")
        max_dec = 32768 // cfg.dec_ratio
        specs["dec_pos_embed"] = ParamSpec((max_dec, d), ("seq", "embed"))
    if cfg.vlm:
        specs["patch_proj"] = ParamSpec((d, d), ("embed", None), init="fan_in")
    if cfg.param_dtype != "float32":
        dt = jnp.dtype(cfg.param_dtype)
        specs = jax.tree.map(
            lambda s: ParamSpec(s.shape, s.axes, dt, s.init, s.scale),
            specs, is_leaf=pm.is_spec,
        )
    return specs


# ---------------------------------------------------------------- attention


def _project_qkv(p, h, cfg: ArchConfig):
    dt = h.dtype
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def _attn_out(p, o, cfg: ArchConfig):
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    if "bo" in p:
        out = out + p["bo"].astype(o.dtype)
    return out


def attn_full(p, x, cfg: ArchConfig, ctx: ShardingCtx, *, positions, causal=True,
              prefix=0, rope=True, kv_out=False):
    """Full-sequence attention block (train / prefill / encoder)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _project_qkv(p, h, cfg)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = attention(
        q, k, v,
        q_pos=positions, k_pos=positions,
        causal=causal, window=cfg.sliding_window, prefix=prefix,
        impl=cfg.attn_impl, chunk=cfg.attn_chunk, unroll=not cfg.scan_layers,
    )
    out = _attn_out(p, o, cfg)
    if kv_out:
        return out, (k, v)
    return out


def attn_cross_full(p, x, enc_out, cfg: ArchConfig, *, kv_out=False):
    """Cross-attention over encoder output (no mask, no rope)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, _, _ = _project_qkv(p, h, cfg)
    dt = h.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    sq, sk = x.shape[1], enc_out.shape[1]
    o = attention(q, k, v, q_pos=jnp.arange(sq), k_pos=jnp.arange(sk),
                  causal=False, impl=cfg.attn_impl, chunk=cfg.attn_chunk,
                  unroll=not cfg.scan_layers)
    out = _attn_out(p, o, cfg)
    if kv_out:
        return out, (k, v)
    return out


def ring_decode_attention(q, k_cache, v_cache, new_k, new_v, pos, window: int):
    """Sliding-window ring-buffer decode (cache slot = position % window)."""
    slot = pos % window
    k_cache = jax.lax.dynamic_update_slice(k_cache, new_k.astype(k_cache.dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, new_v.astype(v_cache.dtype), (0, slot, 0, 0))
    b, _, h, d = q.shape
    kh = k_cache.shape[2]
    qg = q.reshape(b, kh, h // kh, d) * (d ** -0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32)
    s_idx = jnp.arange(window)
    k_pos = pos - (pos - s_idx) % window
    valid = k_pos >= 0
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v_cache)
    return o.reshape(b, 1, h, d), k_cache, v_cache


def attn_decode(p, x, cache, cfg: ArchConfig, ctx: ShardingCtx, *, pos, cross=False,
                rope=True):
    """One-token attention against a KV cache (self or cross)."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _project_qkv(p, h, cfg)
    if rope and not cross:
        posv = jnp.full((1,), 0, jnp.int32) + pos
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
    kc, vc = cache
    if cross:
        # static cache: attend over all encoder positions, no write-back
        o, _, _ = decode_attention(ctx, q, kc, vc, k, v,
                                   jnp.asarray(kc.shape[1] - 1, jnp.int32),
                                   update=False)
        new_cache = (kc, vc)
    elif cfg.sliding_window and kc.shape[1] <= cfg.sliding_window:
        o, kc, vc = ring_decode_attention(q, kc, vc, k, v, pos, kc.shape[1])
        new_cache = (kc, vc)
    else:
        o, kc, vc = decode_attention(ctx, q, kc, vc, k, v, pos,
                                     update_mode=cfg.cache_update)
        new_cache = (kc, vc)
    return _attn_out(p, o, cfg), new_cache


# ------------------------------------------------------------------- blocks


def block_full(p, x, cfg: ArchConfig, ctx: ShardingCtx, *, positions, causal=True,
               prefix=0, rope=True, cross_src=None, build_cache=False):
    """One layer, full-sequence. Returns (x, aux, cache_entry)."""
    aux = jnp.zeros((), jnp.float32)
    cache_entry: Dict[str, Any] = {}
    if cfg.family == "ssm":
        h = rms_norm(x, p["ssm_ln"], cfg.norm_eps)
        if build_cache:
            out, h_fin, conv = ssm_forward(p["ssm"], h, cfg, return_state=True)
            cache_entry["h"], cache_entry["conv"] = h_fin, conv
        else:
            out = ssm_forward(p["ssm"], h, cfg)
        return x + out, aux, cache_entry

    if cfg.hybrid:
        attn_o, kv = attn_full(p["attn"], x, cfg, ctx, positions=positions,
                               causal=causal, prefix=prefix, rope=rope, kv_out=True)
        h = rms_norm(x, p["attn"]["ln"], cfg.norm_eps)
        if build_cache:
            ssm_o, h_fin, conv = ssm_forward(p["ssm"], h, cfg, return_state=True)
            cache_entry["h"], cache_entry["conv"] = h_fin, conv
        else:
            ssm_o = ssm_forward(p["ssm"], h, cfg)
        x = x + 0.5 * (attn_o + ssm_o)
    else:
        attn_o, kv = attn_full(p["attn"], x, cfg, ctx, positions=positions,
                               causal=causal, prefix=prefix, rope=rope, kv_out=True)
        x = x + attn_o
    if build_cache and cfg.has_attention:
        k, v = kv
        if cfg.sliding_window:
            w = cfg.sliding_window
            s_full = k.shape[1]
            if s_full >= w:
                # ring layout: slot = position % w; the last w positions are a
                # cyclic rotation of the slots by (s_full % w)
                k, v = k[:, -w:], v[:, -w:]
                shift = s_full % w
                if shift:
                    k = jnp.roll(k, shift, axis=1)
                    v = jnp.roll(v, shift, axis=1)
        cache_entry["k"], cache_entry["v"] = k, v

    if cross_src is not None:
        if build_cache:
            cross_o, ckv = attn_cross_full(p["cross"], x, cross_src, cfg, kv_out=True)
            cache_entry["cross_k"], cache_entry["cross_v"] = ckv
        else:
            cross_o = attn_cross_full(p["cross"], x, cross_src, cfg)
        x = x + cross_o

    if cfg.moe:
        h = rms_norm(x, p["moe_ln"], cfg.norm_eps)
        moe_o, aux = moe_layer(p["moe"], h, cfg, ctx)
        x = x + moe_o
    elif "mlp" in p:
        m = p["mlp"]
        h = rms_norm(x, m["ln"], cfg.norm_eps)
        x = x + swiglu(h, m["w_gate"].astype(h.dtype), m["w_up"].astype(h.dtype),
                       m["w_down"].astype(h.dtype))
    return x, aux, cache_entry


def block_decode(p, x, layer_cache, cfg: ArchConfig, ctx: ShardingCtx, *, pos):
    """One layer, one token. Returns (x, new_layer_cache)."""
    new_cache: Dict[str, Any] = {}
    if cfg.family == "ssm":
        h = rms_norm(x, p["ssm_ln"], cfg.norm_eps)
        out, st = ssm_decode(p["ssm"], {"h": layer_cache["h"], "conv": layer_cache["conv"]}, h, cfg)
        new_cache.update(st)
        return x + out, new_cache

    rope = not cfg.enc_dec
    if cfg.hybrid:
        attn_o, kv = attn_decode(p["attn"], x, (layer_cache["k"], layer_cache["v"]),
                                 cfg, ctx, pos=pos, rope=rope)
        h = rms_norm(x, p["attn"]["ln"], cfg.norm_eps)
        ssm_o, st = ssm_decode(p["ssm"], {"h": layer_cache["h"], "conv": layer_cache["conv"]}, h, cfg)
        new_cache.update(st)
        new_cache["k"], new_cache["v"] = kv
        x = x + 0.5 * (attn_o + ssm_o)
    else:
        attn_o, kv = attn_decode(p["attn"], x, (layer_cache["k"], layer_cache["v"]),
                                 cfg, ctx, pos=pos, rope=rope)
        new_cache["k"], new_cache["v"] = kv
        x = x + attn_o

    if "cross" in p:
        cross_o, _ = attn_decode(p["cross"], x,
                                 (layer_cache["cross_k"], layer_cache["cross_v"]),
                                 cfg, ctx, pos=pos, cross=True)
        new_cache["cross_k"] = layer_cache["cross_k"]
        new_cache["cross_v"] = layer_cache["cross_v"]
        x = x + cross_o

    if cfg.moe:
        h = rms_norm(x, p["moe_ln"], cfg.norm_eps)
        moe_o, _ = moe_layer(p["moe"], h, cfg, ctx)
        x = x + moe_o
    elif "mlp" in p:
        m = p["mlp"]
        h = rms_norm(x, m["ln"], cfg.norm_eps)
        x = x + swiglu(h, m["w_gate"].astype(h.dtype), m["w_up"].astype(h.dtype),
                       m["w_down"].astype(h.dtype))
    return x, new_cache


# ------------------------------------------------------------ paged decode
#
# The serving engine (repro.serve) replaces the dense [B, max_len] KV caches
# with block pools, one stack per tensor [L, NB, blk, KH*D] that the layer
# loop carries and updates in place: requests own disjoint fixed-size
# pages, a block table maps each request's logical positions onto pool
# blocks, and every request in a round decodes at its OWN position (ragged
# lengths — the dense path's single scalar `pos` becomes a [B] vector).
# Supported families: attention (+ MoE FFN), windowed layers among them
# (a mask, and a decode gather bounded to the window's blocks); SSM/hybrid
# recurrent state and cross-attention keep the dense path.
#
# The paged paths name their parts with `jax.named_scope`, so each HLO
# instruction's `op_name` says which part of the model emitted it:
# `SCOPES` at the top, `SUBSCOPES` inside `attention` and `ffn`. The
# metadata changes no generated code; `model_scope` reads it back.

SCOPES = ("embed", "attention", "ffn", "unembed")
SUBSCOPES = ("qkv", "kv_append", "attend", "attend_window", "out",  # attention
             "router", "experts", "shared_expert")                  # ffn (MoE)


_COND_PART = re.compile(r"cond|branch_\d+_fun")


def model_scope(op_name: str) -> str:
    """The model scope an HLO `op_name` path names: its first component in
    `SCOPES` and the `SUBSCOPES` that follow it (``attention/kv_append``;
    a `lax.cond`'s own components between them skipped), or ``other``
    where it names none (layer-stack slicing, layout copies)."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part in SCOPES:
            path = [part]
            for sub in parts[i + 1:]:
                if _COND_PART.fullmatch(sub):
                    continue    # `lax.cond`'s own components
                if sub not in SUBSCOPES:
                    break
                path.append(sub)
            return "/".join(path)
    return "other"


def _paged_qkv(p, x, cfg: ArchConfig, pos, window: int, full=None):
    """The normed input and q, k, v at positions `pos` [B, S]: q/k norm
    where the layer has it, RoPE unless a full layer goes without
    (`cfg.rope_full`). `full`, where given, is a traced flag: the layer
    is full, not windowed."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = _project_qkv(p, h, cfg)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_full or (window and full is None):
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    elif window:
        q = jnp.where(full, q, apply_rope(q, pos, cfg.rope_theta))
        k = jnp.where(full, k, apply_rope(k, pos, cfg.rope_theta))
    return h, q, k, v


def _attend(attend, window: int, full=None):
    """`attend(window)` under its scope (`attend_window` or `attend`); with
    a traced `full` flag, the full layer's or the windowed one's."""
    def run(w):
        with jax.named_scope("attend_window" if w else "attend"):
            return attend(w)

    if full is None:
        return run(window)
    return jax.lax.cond(full, lambda: run(0), lambda: run(window))


def _paged_out(p, h, o, cfg: ArchConfig):
    """The attention output [B, S, H, D] gated by sigmoid(h W_gate) where
    the layer has a gate, projected, and normed where the layer norms its
    branch (`cfg.sandwich_norm`)."""
    if "w_gate" in p:
        o = o * jax.nn.sigmoid(
            jnp.einsum("bsd,dhk->bshk", h, p["w_gate"].astype(h.dtype)))
    out = _attn_out(p, o, cfg)
    if "post_ln" in p:
        out = rms_norm(out, p["post_ln"], cfg.norm_eps)
    return out


def paged_attn_decode(p, x, k_pools, v_pools, layer, cfg: ArchConfig, *,
                      block_tables, lengths, window: int = 0, full=None):
    """One-token attention against layer `layer` of the paged KV pools.

    x: [B, 1, d]; k_pools/v_pools: [L, NB, blk, KH*D]; lengths: [B] int32
    tokens already stored per request (the new KV row is written at that
    position); `window` > 0: the layer attends only the last `window`
    positions and gathers only their blocks, unless the traced flag `full`
    says it is a full layer. Returns (attn_out, k_pools, v_pools).
    """
    with jax.named_scope("attention"):
        with jax.named_scope("qkv"):
            # [B, 1] per-request decode position
            h, q, k, v = _paged_qkv(p, x, cfg, lengths[:, None], window, full)
        with jax.named_scope("kv_append"):
            k_pools, v_pools = paged_cache_append(
                k_pools, v_pools, layer, block_tables, lengths, k, v)
        o = _attend(lambda w: paged_decode_attention(
            q, k_pools, v_pools, layer, block_tables, lengths + 1, w), window, full)
        with jax.named_scope("out"):
            o = _paged_out(p, h, o, cfg)
    return o, k_pools, v_pools


def paged_block_decode(p, x, k_pools, v_pools, layer, cfg: ArchConfig,
                       ctx: ShardingCtx, *, block_tables, lengths, window: int = 0,
                       full=None):
    """Layer `layer`, one token per request, paged KV. Returns
    (x, k_pools, v_pools)."""
    attn_o, k_pools, v_pools = paged_attn_decode(
        p["attn"], x, k_pools, v_pools, layer, cfg,
        block_tables=block_tables, lengths=lengths, window=window, full=full)
    x = _paged_ffn(p, x + attn_o, cfg, ctx)
    return x, k_pools, v_pools


def run_layers_decode_paged(params, k_pools, v_pools, x, cfg: ArchConfig,
                            ctx: ShardingCtx, *, block_tables, lengths):
    """All layers over the stacked pools [L, NB, blk, KH*D]. Returns
    (x, k_pools, v_pools)."""

    def block_fn(lp, x, kp, vp, layer, window, full=None):
        return paged_block_decode(lp, x, kp, vp, layer, cfg, ctx,
                                  block_tables=block_tables, lengths=lengths,
                                  window=window, full=full)

    return _run_layers_paged(params, k_pools, v_pools, x, cfg, block_fn)


def paged_attn_prefill_chunk(p, x, k_pools, v_pools, layer, cfg: ArchConfig,
                             *, block_tables, start, n_valid, window: int = 0,
                             full=None):
    """Chunk attention against layer `layer` of the paged KV pools
    [L, NB, blk, KH*D] (chunked prefill).

    x: [1, C, d] — a chunk of prompt hidden states at absolute positions
    ``start + i``; the chunk's KV rows are appended into the pool first
    (padding rows masked to the garbage page), then every query attends
    causally over all pool positions <= its own (the last `window` of
    them in a windowed layer, unless the traced flag `full` says it is a
    full layer). Returns (attn_out, k_pools, v_pools).
    """
    with jax.named_scope("attention"):
        with jax.named_scope("qkv"):
            c = x.shape[1]
            pos = (start + jnp.arange(c, dtype=jnp.int32))[None]  # [1, C]
            h, q, k, v = _paged_qkv(p, x, cfg, pos, window, full)
        with jax.named_scope("kv_append"):
            k_pools, v_pools = paged_cache_append_chunk(
                k_pools, v_pools, layer, block_tables, start, k, v, n_valid)
        o = _attend(lambda w: paged_prefill_attention(
            q, k_pools, v_pools, layer, block_tables, pos, w), window, full)
        with jax.named_scope("out"):
            o = _paged_out(p, h, o, cfg)
    return o, k_pools, v_pools


@jax.named_scope("ffn")
def _paged_ffn(p, x, cfg: ArchConfig, ctx: ShardingCtx):
    """The post-attention half every paged block shares (MoE or SwiGLU),
    its output normed where the layer norms its branch."""
    if "moe" in p:
        h = rms_norm(x, p["moe_ln"], cfg.norm_eps)
        y, _ = moe_layer(p["moe"], h, cfg, ctx)
        post = p.get("moe_post_ln")
    elif "mlp" in p:
        m = p["mlp"]
        h = rms_norm(x, m["ln"], cfg.norm_eps)
        y = swiglu(h, m["w_gate"].astype(h.dtype), m["w_up"].astype(h.dtype),
                   m["w_down"].astype(h.dtype))
        post = m.get("post_ln")
    else:
        return x
    if post is not None:
        y = rms_norm(y, post, cfg.norm_eps)
    return x + y


def paged_block_prefill_chunk(p, x, k_pools, v_pools, layer, cfg: ArchConfig,
                              ctx: ShardingCtx, *, block_tables, start, n_valid,
                              window: int = 0, full=None):
    """Layer `layer`, one prefill chunk, paged KV. Returns
    (x, k_pools, v_pools)."""
    attn_o, k_pools, v_pools = paged_attn_prefill_chunk(
        p["attn"], x, k_pools, v_pools, layer, cfg,
        block_tables=block_tables, start=start, n_valid=n_valid, window=window,
        full=full)
    x = _paged_ffn(p, x + attn_o, cfg, ctx)
    return x, k_pools, v_pools


def _layer_groups(params, cfg: ArchConfig):
    """Each stack of alike layers, with the index of its first layer: the
    leading dense layers (`dense_layers`), then `layers`."""
    groups = [(params["layers"], cfg.n_dense_layers)]
    if "dense_layers" in params:
        groups.insert(0, (params["dense_layers"], 0))
    return groups


def _run_layers_paged(params, k_pools, v_pools, x, cfg: ArchConfig, block_fn):
    """Run `block_fn(lp, x, k_pools, v_pools, layer, window[, full])` over every
    layer in order. The whole pool stacks ride in the carry and each layer
    writes and reads its own index of them, so the donated pools are
    updated in place: no layer's pool is sliced out or stacked back."""
    for layers, first in _layer_groups(params, cfg):
        x, k_pools, v_pools = _run_stack_paged(layers, first, (x, k_pools, v_pools),
                                               cfg, block_fn)
    return x, k_pools, v_pools


def _run_stack_paged(layers, first: int, carry, cfg: ArchConfig, block_fn):
    """One stack, its first layer `first`: a scan over its layers (or an
    unroll). Where windowed and full layers sit side by side
    (`cfg.layer_windows`), each scanned layer is told its window and, as a
    traced flag, whether it is full instead (`block_fn`'s `full`), so that
    both kinds run in one scan over the stack as stored."""
    n = jax.tree.leaves(layers)[0].shape[0]
    windows = cfg.layer_windows[first:first + n]
    if not cfg.scan_layers:
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], layers)
            carry = block_fn(lp, *carry, first + i, windows[i])
        return carry
    idx = jnp.arange(first, first + n, dtype=jnp.int32)
    if len(set(windows)) == 1:
        def body(carry, inp):
            lp, layer = inp
            return block_fn(lp, *carry, layer, windows[0]), None

        carry, _ = jax.lax.scan(body, carry, (layers, idx))
        return carry

    window = max(windows)
    full = jnp.asarray([w == 0 for w in windows])

    def mixed(carry, inp):
        lp, layer, is_full = inp
        return block_fn(lp, *carry, layer, window, is_full), None

    carry, _ = jax.lax.scan(mixed, carry, (layers, idx, full))
    return carry


def _paged_embed(params, tokens, cfg: ArchConfig):
    x = embed_lookup(params["embed"], tokens)
    if cfg.embed_scale != 1.0:
        x = x.astype(jnp.float32) * cfg.embed_scale
    return x.astype(jnp.dtype(cfg.dtype))


def prefill_chunk_paged(params, k_pools, v_pools, block_tables, start, batch,
                        n_valid, cfg: ArchConfig, ctx: ShardingCtx = NULL_CTX):
    """One chunk of a request's prefill through the paged pipeline.

    batch["tokens"]: [1, C] right-padded chunk; start: [] int32 absolute
    position of its first token; n_valid: [] int32 real tokens (the rest is
    padding whose KV writes are masked to the garbage page). The request's
    block table must already map every position < start + n_valid — shared
    prefix pages for positions < start (prefix-cache hit), private pages
    for the chunk itself (copy-on-write forked by the pager if the first
    page is shared). Returns (last_logits [1, V] at the chunk's final real
    token, k_pools, v_pools).
    """
    if not supports_paged_decode(cfg):
        raise ValueError(f"paged prefill unsupported for family {cfg.family!r}")
    with jax.named_scope("embed"):
        x = _paged_embed(params, batch["tokens"], cfg)

    def block_fn(lp, x, kp, vp, layer, window, full=None):
        return paged_block_prefill_chunk(lp, x, kp, vp, layer, cfg, ctx,
                                         block_tables=block_tables,
                                         start=start, n_valid=n_valid,
                                         window=window, full=full)

    x, k_pools, v_pools = _run_layers_paged(params, k_pools, v_pools, x, cfg,
                                            block_fn)
    with jax.named_scope("unembed"):
        last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        last = rms_norm(last, params["final_norm"], cfg.norm_eps)
        logits = _unembed(params, last, cfg, ctx)[:, 0]     # [1, V]
    return logits, k_pools, v_pools


def supports_paged_decode(cfg: ArchConfig) -> bool:
    """Families the paged serving engine can drive (attention KV only)."""
    return (cfg.has_attention and not cfg.hybrid and not cfg.enc_dec
            and not cfg.vlm)


def decode_step_paged(params, k_pools, v_pools, block_tables, lengths, batch,
                      cfg: ArchConfig, ctx: ShardingCtx = NULL_CTX):
    """One decode step for a round of ragged requests over paged KV pools.

    k_pools/v_pools: [L, NB, blk, KH*D] (`serve.engine.kv_pool_shape`),
    updated in place when donated. batch["tokens"]: [B, 1]; lengths: [B]
    int32 — each request's stored token count (its new KV row is written
    there, then it attends to lengths+1 positions, or the last `window` of
    them in a windowed layer). Returns (logits [B, 1, V], k_pools, v_pools).
    """
    if not supports_paged_decode(cfg):
        raise ValueError(f"paged decode unsupported for family {cfg.family!r}")
    with jax.named_scope("embed"):
        x = _paged_embed(params, batch["tokens"], cfg)
    x, k_pools, v_pools = run_layers_decode_paged(
        params, k_pools, v_pools, x, cfg, ctx,
        block_tables=block_tables, lengths=lengths)
    with jax.named_scope("unembed"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _unembed(params, x, cfg, ctx)
    return logits, k_pools, v_pools


# --------------------------------------------------------------- layer stack


def run_layers_full(layers, x, cfg: ArchConfig, ctx: ShardingCtx, *, positions,
                    causal=True, prefix=0, rope=True, cross_src=None,
                    build_cache=False):
    """Apply all layers (scan or unrolled). Returns (x, aux_sum, stacked_cache)."""

    def body_fn(x, lp):
        y, aux, cache = block_full(lp, x, cfg, ctx, positions=positions,
                                   causal=causal, prefix=prefix, rope=rope,
                                   cross_src=cross_src, build_cache=build_cache)
        return y, aux, cache

    if cfg.remat:
        body_fn = jax.checkpoint(
            body_fn,
            policy=(jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                    if cfg.remat_policy == "dots" else None),
        )

    if cfg.scan_layers:
        def scan_body(carry, lp):
            x, aux = carry
            y, a, cache = body_fn(x, lp)
            return (y, aux + a), cache
        (x, aux), caches = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)), layers)
        return x, aux, caches

    n = jax.tree.leaves(layers)[0].shape[0]
    aux = jnp.zeros((), jnp.float32)
    cache_list = []
    for i in range(n):
        lp = jax.tree.map(lambda a: a[i], layers)
        x, a, cache = body_fn(x, lp)
        aux = aux + a
        cache_list.append(cache)
    caches = (
        jax.tree.map(lambda *xs: jnp.stack(xs), *cache_list) if cache_list and cache_list[0] else {}
    )
    return x, aux, caches


def run_layers_decode(layers, caches, x, cfg: ArchConfig, ctx: ShardingCtx, *, pos):
    def body(x, inp):
        lp, lc = inp
        y, nc = block_decode(lp, x, lc, cfg, ctx, pos=pos)
        return y, nc

    if cfg.scan_layers:
        def scan_body(x, inp):
            return body(x, inp)
        x, new_caches = jax.lax.scan(scan_body, x, (layers, caches))
        return x, new_caches

    n = jax.tree.leaves(layers)[0].shape[0]
    outs = []
    for i in range(n):
        lp = jax.tree.map(lambda a: a[i], layers)
        lc = jax.tree.map(lambda a: a[i], caches)
        x, nc = body(x, (lp, lc))
        outs.append(nc)
    new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, new_caches


# ------------------------------------------------------------------ frontend


def _embed_in(params, batch, cfg: ArchConfig, ctx: ShardingCtx):
    """Token (+stub-frontend) embedding. Returns (x, positions, text_offset)."""
    dt = jnp.dtype(cfg.dtype)
    tokens = batch["tokens"]
    x = embed_lookup(params["embed"], tokens).astype(dt)
    if cfg.vlm:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)  # gemma-style scaling
        patches = batch["patches"].astype(dt) @ params["patch_proj"].astype(dt)
        x = jnp.concatenate([patches, x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s)
    x = ctx.constrain(x, ("batch", "seq", None))
    return x, positions


def _unembed(params, x, cfg: ArchConfig, ctx: ShardingCtx):
    dt = x.dtype
    table = params.get("lm_head")
    if table is None:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(dt))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, table.astype(dt))
    return ctx.constrain(logits.astype(jnp.float32), ("batch", "seq", "vocab"))


def _xent(logits, targets):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return (lse - gold).mean()


# ------------------------------------------------------------------- top API


def _dense_path(cfg: ArchConfig) -> None:
    if cfg.paged_only:
        raise NotImplementedError(
            f"arch {cfg.name!r} runs through the paged serving paths only "
            "(prefill_chunk_paged, decode_step_paged)")


def loss_fn(params, batch, cfg: ArchConfig, ctx: ShardingCtx = NULL_CTX):
    """Next-token CE loss (+ MoE aux). Returns (loss, metrics)."""
    _dense_path(cfg)
    dt = jnp.dtype(cfg.dtype)
    if cfg.enc_dec:
        frames = batch["frames"].astype(dt)
        enc_x = frames + sinusoid_pos_emb(frames.shape[1], cfg.d_model, dt)[None]
        enc_x = ctx.constrain(enc_x, ("batch", "seq", None))
        enc_pos = jnp.arange(frames.shape[1])
        enc_out, _, _ = run_layers_full(params["enc_layers"], enc_x, cfg, ctx,
                                        positions=enc_pos, causal=False, rope=False)
        enc_out = rms_norm(enc_out, params["enc_norm"], cfg.norm_eps)
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens).astype(dt)
        x = x + params["dec_pos_embed"][: x.shape[1]].astype(dt)[None]
        positions = jnp.arange(x.shape[1])
        x, aux, _ = run_layers_full(params["layers"], x, cfg, ctx,
                                    positions=positions, causal=True, rope=False,
                                    cross_src=enc_out)
    else:
        x, positions = _embed_in(params, batch, cfg, ctx)
        prefix = x.shape[1] - batch["tokens"].shape[1] if cfg.vlm else 0
        x, aux, _ = run_layers_full(params["layers"], x, cfg, ctx,
                                    positions=positions, causal=True,
                                    prefix=prefix, rope=not cfg.enc_dec)
        if cfg.vlm:
            x = x[:, prefix:]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, x, cfg, ctx)
    loss = _xent(logits, batch["targets"])
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux": aux}


def _pad_cache_seq(layer_caches, cfg: ArchConfig, pad_to: Optional[int]):
    """Pad attention caches along seq so decode can write past the prompt."""
    if not layer_caches:
        return layer_caches
    out = dict(layer_caches)
    for key in ("k", "v"):
        if key in out:
            kv = out[key]  # [L, B, S, KH, D]
            target = pad_to
            if cfg.sliding_window:
                target = min(cfg.sliding_window, pad_to) if pad_to else cfg.sliding_window
            if target and kv.shape[2] < target:
                pad = [(0, 0)] * kv.ndim
                pad[2] = (0, target - kv.shape[2])
                out[key] = jnp.pad(kv, pad)
    return out


def prefill(params, batch, cfg: ArchConfig, ctx: ShardingCtx = NULL_CTX,
            pad_to: Optional[int] = None):
    """Build decode state from a full prompt. Returns (cache, last_logits)."""
    _dense_path(cfg)
    dt = jnp.dtype(cfg.dtype)
    cache: Dict[str, Any] = {}
    if cfg.enc_dec:
        frames = batch["frames"].astype(dt)
        enc_x = frames + sinusoid_pos_emb(frames.shape[1], cfg.d_model, dt)[None]
        enc_pos = jnp.arange(frames.shape[1])
        enc_out, _, _ = run_layers_full(params["enc_layers"], enc_x, cfg, ctx,
                                        positions=enc_pos, causal=False, rope=False)
        enc_out = rms_norm(enc_out, params["enc_norm"], cfg.norm_eps)
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens).astype(dt)
        x = x + params["dec_pos_embed"][: x.shape[1]].astype(dt)[None]
        positions = jnp.arange(x.shape[1])
        x, _, layer_caches = run_layers_full(params["layers"], x, cfg, ctx,
                                             positions=positions, causal=True,
                                             rope=False, cross_src=enc_out,
                                             build_cache=True)
        cache["pos"] = jnp.asarray(tokens.shape[1], jnp.int32)
    else:
        x, positions = _embed_in(params, batch, cfg, ctx)
        prefix = x.shape[1] - batch["tokens"].shape[1] if cfg.vlm else 0
        x, _, layer_caches = run_layers_full(params["layers"], x, cfg, ctx,
                                             positions=positions, causal=True,
                                             prefix=prefix, build_cache=True)
        cache["pos"] = jnp.asarray(x.shape[1], jnp.int32)
        if pad_to is not None:
            pad_to = pad_to + prefix  # pad_to counts TEXT positions
    cache["layers"] = _pad_cache_seq(layer_caches, cfg, pad_to)
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, x, cfg, ctx)
    return cache, logits


def decode_step(params, cache, batch, cfg: ArchConfig, ctx: ShardingCtx = NULL_CTX):
    """One decode step for the whole batch. Returns (logits, new_cache)."""
    _dense_path(cfg)
    dt = jnp.dtype(cfg.dtype)
    tokens = batch["tokens"]  # [B, 1]
    pos = cache["pos"]
    x = embed_lookup(params["embed"], tokens).astype(dt)
    if cfg.vlm:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    if cfg.enc_dec:
        max_dec = params["dec_pos_embed"].shape[0]
        x = x + jax.lax.dynamic_slice_in_dim(
            params["dec_pos_embed"], jnp.minimum(pos, max_dec - 1), 1, axis=0
        ).astype(dt)[None, 0:1]
    x, new_layer_caches = run_layers_decode(params["layers"], cache["layers"],
                                            x, cfg, ctx, pos=pos)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, x, cfg, ctx)
    new_cache = {"pos": pos + 1, "layers": new_layer_caches}
    return logits, new_cache


# -------------------------------------------------------------- cache specs


def cache_specs(cfg: ArchConfig, shape: ShapeSuite) -> Dict[str, Any]:
    """Abstract decode-cache tree (ParamSpec) for dry-run input construction."""
    b = shape.global_batch
    hd = cfg.resolved_head_dim
    L = cfg.n_layers
    dt = cfg.dtype
    layer: Dict[str, ParamSpec] = {}
    if cfg.has_attention:
        s_kv = cache_seq_len(cfg, shape)
        seq_axis = "seq" if (cfg.sliding_window and s_kv <= cfg.sliding_window) else "kv_seq"
        layer["k"] = ParamSpec((L, b, s_kv, cfg.kv_heads, hd),
                               ("layers", "batch", seq_axis, "kv_heads", "head_dim"), dt)
        layer["v"] = ParamSpec((L, b, s_kv, cfg.kv_heads, hd),
                               ("layers", "batch", seq_axis, "kv_heads", "head_dim"), dt)
    if cfg.enc_dec:
        s_enc = shape.seq_len
        layer["cross_k"] = ParamSpec((L, b, s_enc, cfg.kv_heads, hd),
                                     ("layers", "batch", "kv_seq", "kv_heads", "head_dim"), dt)
        layer["cross_v"] = ParamSpec((L, b, s_enc, cfg.kv_heads, hd),
                                     ("layers", "batch", "kv_seq", "kv_heads", "head_dim"), dt)
    if cfg.ssm or cfg.hybrid:
        shapes = ssm_cache_shape(cfg, b)
        layer["h"] = ParamSpec((L,) + shapes["h"][0],
                               ("layers", "batch", "ssm_heads", None, "ssm_state"),
                               jnp.float32)
        layer["conv"] = ParamSpec((L,) + shapes["conv"][0],
                                  ("layers", "batch", "width", "conv_dim"), dt)
    return {
        "pos": ParamSpec((), (), jnp.int32),
        "layers": layer,
    }
