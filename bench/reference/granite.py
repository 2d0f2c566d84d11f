"""Plain float32 reference of the granite blocks the benchmark serves.

The equations of granite-3.0 (dense) and granite-3.0 MoE, written out in
`jax.numpy` under `default_matmul_precision("highest")`, with no cache, no
paging and no batching across requests: RMSNorm, rotary position
embeddings (rotate-half), grouped-query attention with a causal mask, a
SwiGLU feed-forward or a top-k softmax router over SwiGLU experts, and the
tied output head. It imports nothing of the program; the weights come from
`bench/weights.py`, drawn again from the seed.

Departures from the published models, which the served program shares:
granite's scalar multipliers (embedding 12, attention 1/64, residual 0.22,
logits 1/8 for the 2b; 1/6 for the MoE) are left out: attention is scaled
by 1/sqrt(head_dim), and the embedding, the residual branches and the
logits are not scaled. The configuration files list them under `reduced`.

`quant="fp8"` is the control: every matrix product takes operands rounded
to float8 (e4m3) with one scale per tensor row, as an fp8 serving path
would; everything else stays float32.

Memory: the layers run one at a time over all sequences, each layer's
weights drawn just before use, and attention walks the queries in blocks,
so a 4096-token sequence of granite-3-2b fits beside nothing else on one
16 GB chip.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

Q_BLOCK = 512
HEAD_ROWS = 256
FP8_MAX = 448.0


def _q(x, quant: Optional[str]):
    """Round a matmul operand to the control's precision (rows scaled)."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [S, H, D]; rotate-half rotary embedding at positions pos [S]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _wq(w, quant, axis):
    """Per-output-channel quantization of a weight: `axis` lists the
    contracted dimensions, scaled over together."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def attention(p, x, m, quant):
    """x [S, d] -> [S, d]; causal GQA over the S positions of one sequence."""
    s = x.shape[0]
    h, kh, hd = m["h"], m["kh"], m["hd"]
    g = h // kh
    hn = _q(rms_norm(x, p["attn.ln"], m["eps"]), quant)
    q = jnp.einsum("sd,dhk->shk", hn, _wq(p["attn.wq"], quant, (0,)))
    k = jnp.einsum("sd,dhk->shk", hn, _wq(p["attn.wk"], quant, (0,)))
    v = jnp.einsum("sd,dhk->shk", hn, _wq(p["attn.wv"], quant, (0,)))
    pos = jnp.arange(s)
    q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
    # query head i reads kv head i // g
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kq, vq = _q(k, quant), _q(jnp.swapaxes(v, 0, 2), quant)  # v as [D, H, S]

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=0)
        sc = jnp.einsum("qhd,khd->hqk", _q(qb, quant), kq) / np.sqrt(hd)
        qpos = q0 + jnp.arange(Q_BLOCK)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,dhk->qhd", _q(pr, quant), vq)

    o = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK))
    o = o.reshape(s, h * hd)
    wo = _wq(p["attn.wo"], quant, (0, 1)).reshape(h * hd, -1)
    return _q(o, quant) @ wo


def swiglu(hn, wg, wu, wd, quant):
    a = _q(hn, quant)
    z = jax.nn.silu(a @ _wq(wg, quant, (0,))) * (a @ _wq(wu, quant, (0,)))
    return _q(z, quant) @ _wq(wd, quant, (0,))


def ffn(p, x, m, quant):
    if m["E"]:
        hn = rms_norm(x, p["moe_ln"], m["eps"])
        logits = _q(hn, quant) @ _wq(p["moe.router"], quant, (0,))
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(probs, m["k"])
        gates = top / jnp.sum(top, axis=-1, keepdims=True)
        weight = jnp.sum(jax.nn.one_hot(idx, m["E"]) * gates[..., None], axis=1)

        def expert(carry, e):
            y = swiglu(hn, p["moe.w_gate"][e], p["moe.w_up"][e],
                       p["moe.w_down"][e], quant)
            return carry + weight[:, e][:, None] * y, None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m["E"]))
        return out
    hn = rms_norm(x, p["mlp.ln"], m["eps"])
    return swiglu(hn, p["mlp.w_gate"], p["mlp.w_up"], p["mlp.w_down"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(p, x, *, m, quant):
    def one(xs):
        xs = xs + attention(p, xs, dict(m), quant)
        return xs + ffn(p, xs, dict(m), quant)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(g, x, *, m, quant):
    hn = rms_norm(x, g["final_norm"], dict(m)["eps"])
    return _q(hn, quant) @ _wq(g["embed"], quant, (1,)).T


def logits(cfg: dict, seed: int, seqs: Sequence[Sequence[int]],
           positions: Sequence[Sequence[int]], *, pad_to: int,
           quant: Optional[str] = None) -> List[np.ndarray]:
    """Logits [len(positions[i]), vocab] of each sequence at the given
    positions (the next-token distribution after each), from a full causal
    forward pass. Sequences are right-padded to `pad_to`, a multiple of the
    query block; padding sits after every position read, so it changes
    nothing that is returned."""
    m = W.dims(cfg)
    if pad_to % Q_BLOCK:
        pad_to = -(-pad_to // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens > pad_to {pad_to}")
        toks[i, :len(s)] = s
    key = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        g = W.global_f32(cfg, seed)
        x = g["embed"][jnp.asarray(toks)]
        for layer in range(m["L"]):
            p = W.layer_f32(cfg, seed, layer)
            x = _layer(p, x, m=key, quant=quant)
            del p
        # one head call over every position read, padded to a fixed count
        at = [(i, p) for i, pos in enumerate(positions) for p in pos]
        n = -(-max(len(at), 1) // HEAD_ROWS) * HEAD_ROWS
        si = np.zeros(n, np.int32)
        pi = np.zeros(n, np.int32)
        si[:len(at)] = [i for i, _ in at]
        pi[:len(at)] = [p for _, p in at]
        rows = x[jnp.asarray(si), jnp.asarray(pi)]
        flat = np.asarray(_head(g, rows, m=key, quant=quant))[:len(at)]
    out, o = [], 0
    for pos in positions:
        out.append(flat[o:o + len(pos)])
        o += len(pos)
    return out
