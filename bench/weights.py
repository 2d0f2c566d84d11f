"""Seeded random weights of a granite block stack, made by the benchmark.

The benchmark, not the program, makes the weights: the served model gets
them in the dtype it serves (bfloat16), built on the device in one jitted
call, and the plain reference makes the same values again, layer by layer,
in float32. Both go through `leaf`, so a value is the bfloat16 rounding of
one uniform draw, whichever side asks for it.

Names and shapes follow the layer equations (``attn.wq`` is
``[d, heads, head_dim]``); `bench/drivers/serve.py` maps them onto the
program's parameter tree and checks that the shapes agree.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

SERVED_DTYPE = jnp.bfloat16


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


def layer_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape of one layer's leaf, init) for every per-layer leaf.
    init is ``gain`` (norm weights near 1) or ``fan_in`` (uniform with
    variance 1/fan_in over the contracted dimension)."""
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
    return s


def _fan_in(name: str, shape) -> int:
    # the contracted dimensions of each projection
    if name in ("attn.wo",):
        return shape[0] * shape[1]
    if name.startswith("moe.w_"):
        return shape[1]
    return shape[0]


def base_key(seed: int):
    """A key from a seed of any size (the driver's seeds pass 2**32)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf(key, index: int, layer: int, shape, init: str, scale: float):
    """One leaf (or one layer of a stacked leaf), rounded to bfloat16."""
    k = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    if init == "gain":
        u = jax.random.uniform(k, shape, jnp.float32, -0.2, 0.2)
        return (1.0 + u).astype(SERVED_DTYPE)
    a = scale * math.sqrt(3.0)
    return jax.random.uniform(k, shape, jnp.float32, -a, a).astype(SERVED_DTYPE)


EMBED_STD = 0.02


def _global_leaves(cfg: dict):
    m = dims(cfg)
    # (name, index, shape, init, scale); indices past the layer leaves
    return (("embed", 1000, (m["V"], m["d"]), "fan_in", EMBED_STD),
            ("final_norm", 1001, (m["d"],), "gain", 0.0))


def make_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every leaf, stacked over layers, in bfloat16, on the default device.
    One jitted call; each layer is drawn inside a `lax.map`, so the float32
    draw of one layer is the largest temporary."""
    L = dims(cfg)["L"]
    shapes = layer_shapes(cfg)

    def build(key):
        out = {}
        for i, (name, (shape, init)) in enumerate(sorted(shapes.items())):
            scale = 1.0 / math.sqrt(_fan_in(name, shape))
            out[name] = jax.lax.map(
                lambda l, i=i, shape=shape, init=init, scale=scale:
                    leaf(key, i, l, shape, init, scale),
                jnp.arange(L))
        for name, i, shape, init, scale in _global_leaves(cfg):
            out[name] = leaf(key, i, 0, shape, init, scale)
        return out

    return jax.jit(build)(base_key(seed))


def layer_f32(cfg: dict, seed: int, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves as float32 (the bfloat16 values, widened)."""
    shapes = layer_shapes(cfg)
    key = base_key(seed)
    out = {}
    for i, (name, (shape, init)) in enumerate(sorted(shapes.items())):
        scale = 1.0 / math.sqrt(_fan_in(name, shape))
        out[name] = _leaf_jit(key, i, layer, shape, init, scale).astype(jnp.float32)
    return out


def global_f32(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    key = base_key(seed)
    return {name: _leaf_jit(key, i, 0, shape, init, scale).astype(jnp.float32)
            for name, i, shape, init, scale in _global_leaves(cfg)}


_leaf_jit = jax.jit(leaf, static_argnums=(1, 3, 4, 5))
