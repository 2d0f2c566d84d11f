"""The afmoe block family (arcee-ai Trinity): its plain float32 reference,
the leaves its weights are drawn as, and the sizes its work is counted from.

A configuration file names it (`"reference": "afmoe"`). The equations, as
`AfmoeForCausalLM` in HF transformers computes them; they are recalled,
not re-read (no network, and the transformers installed here has no
afmoe), and each configuration file says so under `assumed`:

* ``x0 = embed[ids] * sqrt(hidden)`` (muP, `mup_enabled`);
* each layer ``h = x + post_attn_norm(attn(input_norm(x)))``, then
  ``x' = h + post_mlp_norm(mlp(pre_mlp_norm(h)))``: four RMSNorms;
* attention: ``q = q_norm(x Wq)``, ``k = k_norm(x Wk)`` (RMSNorm over
  head_dim, one gain shared by the heads); RoPE (rotate-half) on sliding
  layers only, full layers have no positional encoding; a sliding layer's
  query at i sees keys i - window < j <= i, a full layer's j <= i; scale
  1/sqrt(head_dim); grouped-query heads; the output
  ``o_proj(attn ⊙ sigmoid(x W_gate))``;
* the first `num_dense_layers` layers a SwiGLU of `intermediate_size`;
  the others experts: scores ``s = sigmoid(x W_r)`` in float32 over all
  the router's experts, the `num_experts_per_tok` chosen by ``s + b``
  (`expert_bias`, which moves the choice and not the weights), weighted
  by ``route_scale * s / sum(s of those chosen)``, plus a shared SwiGLU
  expert every token passes through; every expert a SwiGLU of
  `moe_intermediate_size`;
* the final RMSNorm and the untied head ``lm_head``.

The share of the experts: the router has `router_experts` outputs, and
only the `num_experts` routed experts with ids [0, num_experts) are held
(one chip's share of expert parallelism). What the others would add is
left out, here as in the program. No cache, no paging, no batching across
requests, `default_matmul_precision("highest")`. It imports nothing of the
program; the weights come from `bench/weights.py`, drawn again from the
seed.

`quant="fp8"` is the control, as granite's: every matrix product takes
operands rounded to float8 (e4m3), scaled per row.

Leaves: the attention of every layer is stacked over all layers (a
per-layer leaf); the feed-forward halves differ between the leading dense
layers and the expert layers, so each is a global leaf named by its place
in the program's tree (``dense_layers.mlp.w_gate``, ``layers.moe.w_gate``),
stacked over its own layers.

Memory: layers run one at a time over all sequences, each drawing its
weights just before use (an expert leaf is drawn whole in bfloat16 and one
layer kept), attention walks the queries in blocks, and the 200k-wide head
takes the rows read in blocks, each brought to the host as it is done.
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
from bench.reference import granite as G

Q_BLOCK = 512
HEAD_ROWS = 256
BIAS_STD = 0.02   # expert_bias: small, nonzero, drawn from the seed


def dims(cfg: dict) -> dict:
    """The sizes an afmoe block needs, read from a configuration file."""
    d = cfg["hidden_size"]
    return dict(
        d=d, h=cfg["num_attention_heads"], kh=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        E=cfg["router_experts"], Eh=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], nd=cfg["num_dense_layers"],
        fs=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        eps=cfg["rms_norm_eps"],
        theta=cfg["rope_theta"], scale=cfg["route_scale"],
        emb=math.sqrt(d) if cfg["mup_enabled"] else 1.0)


def layer_kinds(cfg: dict):
    """Per layer (window, rope): a sliding layer attends its window with
    RoPE; a full layer attends every earlier key with none."""
    w = cfg["sliding_window"]
    return [(w, True) if t == "sliding_attention" else (0, False)
            for t in cfg["layer_types"]]


def leaves(cfg: dict) -> W.Leaves:
    m = dims(cfg)
    d, h, kh, hd, f, fe, fs = m["d"], m["h"], m["kh"], m["hd"], m["f"], m["fe"], m["fs"]
    E, Eh, nd, nm = m["E"], m["Eh"], m["nd"], m["L"] - m["nd"]
    attn = {
        "attn.ln": ((d,), "gain", 0.0),
        "attn.wq": ((d, h, hd), "fan_in", 1 / math.sqrt(d)),
        "attn.wk": ((d, kh, hd), "fan_in", 1 / math.sqrt(d)),
        "attn.wv": ((d, kh, hd), "fan_in", 1 / math.sqrt(d)),
        "attn.wo": ((h, hd, d), "fan_in", 1 / math.sqrt(h * hd)),
        "attn.q_norm": ((hd,), "gain", 0.0),
        "attn.k_norm": ((hd,), "gain", 0.0),
        "attn.w_gate": ((d, h, hd), "fan_in", 1 / math.sqrt(d)),
        "attn.post_ln": ((d,), "gain", 0.0),
    }
    groups = {
        "dense_layers.mlp.ln": ((nd, d), "gain", 0.0),
        "dense_layers.mlp.w_gate": ((nd, d, f), "fan_in", 1 / math.sqrt(d)),
        "dense_layers.mlp.w_up": ((nd, d, f), "fan_in", 1 / math.sqrt(d)),
        "dense_layers.mlp.w_down": ((nd, f, d), "fan_in", 1 / math.sqrt(f)),
        "dense_layers.mlp.post_ln": ((nd, d), "gain", 0.0),
        "layers.moe_ln": ((nm, d), "gain", 0.0),
        "layers.moe_post_ln": ((nm, d), "gain", 0.0),
        "layers.moe.router": ((nm, d, E), "fan_in", 1 / math.sqrt(d)),
        "layers.moe.expert_bias": ((nm, E), "fan_in", BIAS_STD),
        "layers.moe.w_gate": ((nm, Eh, d, fe), "fan_in", 1 / math.sqrt(d)),
        "layers.moe.w_up": ((nm, Eh, d, fe), "fan_in", 1 / math.sqrt(d)),
        "layers.moe.w_down": ((nm, Eh, fe, d), "fan_in", 1 / math.sqrt(fe)),
        "layers.moe.shared.w_gate": ((nm, d, fs), "fan_in", 1 / math.sqrt(d)),
        "layers.moe.shared.w_up": ((nm, d, fs), "fan_in", 1 / math.sqrt(d)),
        "layers.moe.shared.w_down": ((nm, fs, d), "fan_in", 1 / math.sqrt(fs)),
    }
    glob = {"embed": (1000, (m["V"], d), "fan_in", G.EMBED_STD),
            "final_norm": (1001, (d,), "gain", 0.0),
            "lm_head": (1002, (d, m["V"]), "fan_in", G.EMBED_STD)}
    for i, (name, leaf) in enumerate(sorted(groups.items())):
        glob[name] = (1003 + i,) + leaf
    return W.Leaves(m["L"], attn, glob)


def _whole(top: int) -> float:
    return int(top) if top == int(top) else top


def sizes(cfg: dict) -> work.Sizes:
    """Per layer what it holds and what one token multiplies through: the
    gated attention; a dense SwiGLU, or the router, the shared expert and
    the held experts, of which a token reaches top_k * held / routed on
    average. The sliding layers attend their window. The head is untied."""
    m = dims(cfg)
    d, h, kh, hd = m["d"], m["h"], m["kh"], m["hd"]
    attn = 2 * d * h * hd + 2 * d * kh * hd + h * hd * d + 2 * hd + 2 * d
    kv = 2 * kh * hd * work.BF16
    expert = 3 * d * m["fe"]
    out = []
    for i, (window, _) in enumerate(layer_kinds(cfg)):
        w = window or None
        if i < m["nd"]:
            p = attn + 3 * d * m["f"] + 2 * d
            out.append(work.Layer(p, p, kv, 4 * h * hd, window=w))
            continue
        top = _whole(m["k"] * m["Eh"] / m["E"])
        rest = attn + 2 * d + d * m["E"] + m["E"] + 3 * d * m["fs"]
        out.append(work.Layer(rest + m["Eh"] * expert, rest + top * expert, kv,
                              4 * h * hd, window=w, experts=m["Eh"], top_k=top,
                              expert_params=expert))
    return work.Sizes(tuple(out), head_params=2 * m["V"] * d + d,
                      unembed_params=m["V"] * d)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _layer_of(key, index, shape, init, scale, j):
    """Layer `j` of a stacked global leaf, as float32."""
    return W.leaf(key, index, 0, shape, init, scale)[j].astype(jnp.float32)


def layer_f32(cfg: dict, spec: W.Leaves, seed: int, layer: int) -> dict:
    """One layer's leaves as float32, named as in the program's layer
    (``attn.wq``, ``mlp.w_gate`` or ``moe.w_gate``, ``moe_ln``)."""
    nd = cfg["num_dense_layers"]
    group, j = ("dense_layers.", layer) if layer < nd else ("layers.", layer - nd)
    p = W.layer_f32(spec, seed, layer)
    key = W.base_key(seed)
    for name, (i, shape, init, scale) in spec.glob.items():
        if name.startswith(group):
            p[name[len(group):]] = _layer_of(key, i, shape, init, scale, j)
    return p


def attention(p, x, m, window, rope, quant):
    """x [S, d] -> [S, d]: the gated attention branch, its norm applied."""
    _q, _wq = G._q, G._wq
    s = x.shape[0]
    h, kh, hd = m["h"], m["kh"], m["hd"]
    g = h // kh
    hn = _q(G.rms_norm(x, p["attn.ln"], m["eps"]), quant)
    q = jnp.einsum("sd,dhk->shk", hn, _wq(p["attn.wq"], quant, (0,)))
    k = jnp.einsum("sd,dhk->shk", hn, _wq(p["attn.wk"], quant, (0,)))
    v = jnp.einsum("sd,dhk->shk", hn, _wq(p["attn.wv"], quant, (0,)))
    q = G.rms_norm(q, p["attn.q_norm"], m["eps"])
    k = G.rms_norm(k, p["attn.k_norm"], m["eps"])
    pos = jnp.arange(s)
    if rope:
        q, k = G.rope(q, pos, m["theta"]), G.rope(k, pos, m["theta"])
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kq, vq = _q(k, quant), _q(jnp.swapaxes(v, 0, 2), quant)  # v as [D, H, S]

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=0)
        sc = jnp.einsum("qhd,khd->hqk", _q(qb, quant), kq) / np.sqrt(hd)
        qpos = (q0 + jnp.arange(Q_BLOCK))[None, :, None]
        keep = pos[None, None, :] <= qpos
        if window:
            keep &= pos[None, None, :] > qpos - window
        pr = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,dhk->qhd", _q(pr, quant), vq)

    o = jax.lax.map(block, jnp.arange(0, s, Q_BLOCK)).reshape(s, h, hd)
    gate = jnp.einsum("sd,dhk->shk", hn, _wq(p["attn.w_gate"], quant, (0,)))
    o = (o * jax.nn.sigmoid(gate)).reshape(s, h * hd)
    wo = _wq(p["attn.wo"], quant, (0, 1)).reshape(h * hd, -1)
    return G.rms_norm(_q(o, quant) @ wo, p["attn.post_ln"], m["eps"])


def routing(p, hn, m, quant):
    """[S, routed experts] weights: sigmoid scores, the top k chosen by
    score + bias, weighted by their scores normalised and scaled."""
    logits = G._q(hn, quant) @ G._wq(p["moe.router"], quant, (0,))
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + p["moe.expert_bias"], m["k"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    gates = m["scale"] * top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, m["E"]) * gates[..., None], axis=1)


def ffn(p, x, m, dense, quant):
    """x [S, d] -> [S, d]: the feed-forward branch, its norm applied."""
    if dense:
        hn = G.rms_norm(x, p["mlp.ln"], m["eps"])
        y = G.swiglu(hn, p["mlp.w_gate"], p["mlp.w_up"], p["mlp.w_down"], quant)
        return G.rms_norm(y, p["mlp.post_ln"], m["eps"])
    hn = G.rms_norm(x, p["moe_ln"], m["eps"])
    weight = routing(p, hn, m, quant)[:, :m["Eh"]]     # the held experts' share

    def expert(carry, e):
        y = G.swiglu(hn, p["moe.w_gate"][e], p["moe.w_up"][e], p["moe.w_down"][e],
                     quant)
        return carry + weight[:, e][:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m["Eh"]))
    shared = G.swiglu(hn, p["moe.shared.w_gate"], p["moe.shared.w_up"],
                      p["moe.shared.w_down"], quant)
    return G.rms_norm(shared + routed, p["moe_post_ln"], m["eps"])


@functools.partial(jax.jit, static_argnames=("m", "window", "rope", "dense", "quant"))
def _layer(p, x, *, m, window, rope, dense, quant):
    def one(xs):
        xs = xs + attention(p, xs, dict(m), window, rope, quant)
        return xs + ffn(p, xs, dict(m), dense, quant)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def untied_head(final_norm, lm_head, x, *, eps, quant):
    hn = G.rms_norm(x, final_norm, eps)
    return G._q(hn, quant) @ G._wq(lm_head, quant, (0,))


def logits(cfg: dict, seed: int, seqs: Sequence[Sequence[int]],
           positions: Sequence[Sequence[int]], *, pad_to: int,
           quant: Optional[str] = None) -> List[np.ndarray]:
    """Logits [len(positions[i]), vocab] of each sequence at the given
    positions, from a full causal forward pass over sequences right-padded
    to `pad_to` (rounded up to the query block)."""
    m = dims(cfg)
    spec = leaves(cfg)
    key = tuple(sorted(m.items()))
    pad_to = -(-pad_to // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens > pad_to {pad_to}")
        toks[i, :len(s)] = s
    base = W.base_key(seed)

    def glob(name):
        i, shape, init, scale = spec.glob[name]
        return W.leaf(base, i, 0, shape, init, scale).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        x = glob("embed")[jnp.asarray(toks)] * m["emb"]
        for layer, (window, rope) in enumerate(layer_kinds(cfg)):
            p = layer_f32(cfg, spec, seed, layer)
            x = _layer(p, x, m=key, window=window, rope=rope,
                       dense=layer < m["nd"], quant=quant)
            del p
        at = [(i, q) for i, pos in enumerate(positions) for q in pos]
        si = jnp.asarray([i for i, _ in at], jnp.int32)
        pi = jnp.asarray([q for _, q in at], jnp.int32)
        rows = x[si, pi]
        del x
        final_norm, lm_head = glob("final_norm"), glob("lm_head")
        n = rows.shape[0]
        pad = -(-max(n, 1) // HEAD_ROWS) * HEAD_ROWS - n
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        out = [np.asarray(untied_head(final_norm, lm_head, rows[b:b + HEAD_ROWS],
                                      eps=m["eps"], quant=quant))
               for b in range(0, n, HEAD_ROWS)]
    return G.split(np.concatenate(out)[:n] if out else np.zeros((0, m["V"])),
                   positions)
