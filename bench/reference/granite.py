"""The granite block family: its plain float32 reference, the leaves its
weights are drawn as, and the sizes its work is counted from.

A configuration file names its family (`"reference": "granite"`), and the
benchmark loads `bench/reference/<family>.py` by that name. A family module
gives `leaves` (for `bench/weights.py`), `sizes` (for `bench/work.py`) and
`logits` (for `bench/check.py`).

The equations of granite-3.0 (dense) and granite-3.0 MoE, written out in
`jax.numpy` under `default_matmul_precision("highest")`, with no cache, no
paging and no batching across requests: RMSNorm, rotary position
embeddings (rotate-half), grouped-query attention with a causal mask, a
SwiGLU feed-forward or a top-k softmax router over SwiGLU experts, and the
output head tied to the embedding, as both published files tie it. It
imports nothing of the program; the weights come from `bench/weights.py`,
drawn again from the seed.

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
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench import work

Q_BLOCK = 512
HEAD_ROWS = 256
FP8_MAX = 448.0
EMBED_STD = 0.02


def dims(cfg: dict) -> dict:
    """The sizes a granite block needs, read from a configuration file."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(
        d=d, h=h, kh=cfg["num_key_value_heads"], hd=cfg.get("head_dim") or d // h,
        L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
        f=cfg["intermediate_size"], E=cfg.get("num_local_experts", 0),
        k=cfg.get("num_experts_per_tok", 0), eps=cfg["rms_norm_eps"],
        theta=cfg["rope_theta"])


def _fan_in(name: str, shape) -> int:
    # the contracted dimensions of each projection
    if name in ("attn.wo",):
        return shape[0] * shape[1]
    if name.startswith("moe.w_"):
        return shape[1]
    return shape[0]


def leaves(cfg: dict) -> W.Leaves:
    """Every leaf of the served model: per layer, attention and a SwiGLU
    feed-forward or a router over SwiGLU experts; the embedding (which is
    also the head) and the final norm."""
    m = dims(cfg)
    d, h, kh, hd, f, E = m["d"], m["h"], m["kh"], m["hd"], m["f"], m["E"]
    s = {
        "attn.ln": ((d,), "gain"),
        "attn.wq": ((d, h, hd), "fan_in"),
        "attn.wk": ((d, kh, hd), "fan_in"),
        "attn.wv": ((d, kh, hd), "fan_in"),
        "attn.wo": ((h, hd, d), "fan_in"),
    }
    if E:
        s.update({
            "moe_ln": ((d,), "gain"),
            "moe.router": ((d, E), "fan_in"),
            "moe.w_gate": ((E, d, f), "fan_in"),
            "moe.w_up": ((E, d, f), "fan_in"),
            "moe.w_down": ((E, f, d), "fan_in"),
        })
    else:
        s.update({
            "mlp.ln": ((d,), "gain"),
            "mlp.w_gate": ((d, f), "fan_in"),
            "mlp.w_up": ((d, f), "fan_in"),
            "mlp.w_down": ((f, d), "fan_in"),
        })
    per_layer = {name: (shape, init, 1.0 / math.sqrt(_fan_in(name, shape)))
                 for name, (shape, init) in s.items()}
    return W.Leaves(m["L"], per_layer, {
        "embed": (1000, (m["V"], d), "fan_in", EMBED_STD),
        "final_norm": (1001, (d,), "gain", 0.0)})


def sizes(cfg: dict) -> work.Sizes:
    """What `bench/work.py` counts: every layer alike, the head tied."""
    m = dims(cfg)
    d, h, kh, hd, f, E, k = m["d"], m["h"], m["kh"], m["hd"], m["f"], m["E"], m["k"]
    attn = d * h * hd + 2 * d * kh * hd + h * hd * d + d
    kv = 2 * kh * hd * work.BF16
    if E:
        params = attn + d + d * E + E * 3 * d * f
        layer = work.Layer(params, params - (E - k) * 3 * d * f, kv, 4 * h * hd,
                           experts=E, top_k=k, expert_params=3 * d * f)
    else:
        params = attn + d + 3 * d * f
        layer = work.Layer(params, params, kv, 4 * h * hd)
    return work.Sizes((layer,) * m["L"], head_params=m["V"] * d + d,
                      unembed_params=m["V"] * d)


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
def tied_head(g, x, *, m, quant):
    """The output head: the final norm, then the embedding's rows."""
    hn = rms_norm(x, g["final_norm"], dict(m)["eps"])
    return _q(hn, quant) @ _wq(g["embed"], quant, (1,)).T


def hidden(cfg: dict, spec: W.Leaves, seed: int, seqs: Sequence[Sequence[int]],
           positions: Sequence[Sequence[int]], *, pad_to: int,
           quant: Optional[str] = None):
    """(global leaves, rows, key): granite's blocks run over each sequence,
    and the last block's output at every position read, in order, padded
    to a multiple of `HEAD_ROWS` rows; `key` is the static form of `dims`
    that a jitted head takes as `m`. Sequences are right-padded to
    `pad_to`, a multiple of the query block; padding sits after every
    position read, so it changes nothing that is returned. Call it under
    `default_matmul_precision("highest")`, as `logits` does. A family with
    granite's block and leaves of its own (`spec`) puts its head on these
    rows, and `split` cuts them back into sequences."""
    m = dims(cfg)
    if pad_to % Q_BLOCK:
        pad_to = -(-pad_to // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens > pad_to {pad_to}")
        toks[i, :len(s)] = s
    key = tuple(sorted(m.items()))
    g = W.global_f32(spec, seed)
    x = g["embed"][jnp.asarray(toks)]
    for layer in range(m["L"]):
        p = W.layer_f32(spec, seed, layer)
        x = _layer(p, x, m=key, quant=quant)
        del p
    # one head call over every position read, padded to a fixed count
    at = [(i, p) for i, pos in enumerate(positions) for p in pos]
    n = -(-max(len(at), 1) // HEAD_ROWS) * HEAD_ROWS
    si = np.zeros(n, np.int32)
    pi = np.zeros(n, np.int32)
    si[:len(at)] = [i for i, _ in at]
    pi[:len(at)] = [p for _, p in at]
    return g, x[jnp.asarray(si), jnp.asarray(pi)], key


def split(flat, positions: Sequence[Sequence[int]]) -> List[np.ndarray]:
    """The head's rows, cut into one array per sequence (padding dropped)."""
    flat = np.asarray(flat)
    out, o = [], 0
    for pos in positions:
        out.append(flat[o:o + len(pos)])
        o += len(pos)
    return out


def logits(cfg: dict, seed: int, seqs: Sequence[Sequence[int]],
           positions: Sequence[Sequence[int]], *, pad_to: int,
           quant: Optional[str] = None) -> List[np.ndarray]:
    """Logits [len(positions[i]), vocab] of each sequence at the given
    positions (the next-token distribution after each), from a full causal
    forward pass (`hidden`) and the tied head."""
    with jax.default_matmul_precision("highest"):
        g, rows, key = hidden(cfg, leaves(cfg), seed, seqs, positions,
                              pad_to=pad_to, quant=quant)
        return split(tied_head(g, rows, m=key, quant=quant), positions)
